"""The paged decode kernel as a Laguna-family model uses it: 6 and 9
query heads a KV head beside Llama's 4, and a window bound (interpreter,
CPU; tests/test_tpu_compile.py compiles the same at real widths)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import cached_attention
from ray_tpu.ops.paged_attention import paged_attention


@pytest.mark.parametrize("group", [4, 6, 9])
@pytest.mark.parametrize("window", [None, 20], ids=["full", "window20"])
def test_decode_kernel_by_group_and_window(group, window):
    """The paged decode kernel at 4, 6 and 9 query heads a KV head, with
    and without a window bound (a table that starts at `starts`),
    against `cached_attention` under the same mask."""
    rs = np.random.RandomState(group)
    hkv, d, page, b, n_pages = 2, 16, 8, 3, 32
    pool_k = jnp.asarray(rs.randn(n_pages * page, hkv, d), jnp.float32)
    pool_v = jnp.asarray(rs.randn(n_pages * page, hkv, d), jnp.float32)
    q = jnp.asarray(rs.randn(b, 1, hkv * group, d), jnp.float32)
    lens = np.asarray([5, 37, 64])
    pages = rs.permutation(np.arange(1, n_pages))[:b * 8].reshape(b, 8)
    if window is None:
        starts, tables = None, pages
    else:
        first = np.maximum(0, lens - window) // page
        starts = first * page
        tables = np.zeros((b, window // page + 2), np.int64)
        for i in range(b):
            live = pages[i, first[i]:(lens[i] - 1) // page + 1]
            tables[i, :len(live)] = live
    got = paged_attention(q, pool_k, pool_v, jnp.asarray(tables),
                          jnp.asarray(lens), page_size=page,
                          window=window, starts=None if starts is None
                          else jnp.asarray(starts))
    width = 64
    pos = np.broadcast_to(np.arange(width), (b, width))
    slots = np.take_along_axis(pages, pos // page, axis=1) * page + pos % page
    want = cached_attention(q, pool_k, pool_v, jnp.asarray(slots),
                            jnp.asarray(pos),
                            jnp.asarray(pos < lens[:, None]),
                            jnp.asarray(lens[:, None] - 1), window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
