"""GSPMD training-step construction for every model family.

This is the TPU-native equivalent of the reference's prepare_model
DDP/FSDP wrapping (reference: python/ray/train/torch/train_loop_utils.py
:158-186): instead of wrapping a module, we place parameters with
PartitionSpecs on a named mesh and jit one train step; XLA inserts the
all-gathers/reduce-scatters (fsdp), all-reduces (dp) and collective
matmuls (tp) over ICI.

`build_train_state(model=...)` builds the step of whatever family
`ray_tpu.models.resolve` finds (the dense Llama block, the expert-layer
family), from the family's train side; it hands out the step's counters
beside the loss and the jitted value_and_grad the step differentiates.
`build_llama_train_state` is the same for a LlamaConfig in the older
three-value form; `build_llama_stage_state` builds one MPMD pipeline
stage of the Llama block (the expert family has no stage module yet).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple


def _mesh_attention_kernel(mesh) -> Optional[Callable]:
    """The attention kernel a step partitioned over `mesh` needs; None
    (the model's default) on a one-device mesh."""
    if mesh.shape.get("sp", 1) > 1:
        # sequence-parallel mesh: ring attention rotates KV over ICI
        from ray_tpu.ops.ring_attention import make_ring_attention

        return make_ring_attention(mesh)
    if mesh.size > 1:
        from ray_tpu.models.llama import make_mesh_attention

        return make_mesh_attention(mesh)
    return None


def _init_opt_state(mesh, tx, params):
    """`tx.init(params)` with every subtree that mirrors the params
    (adam's moments) sharded as the params are, the rest replicated.
    Left to itself the partitioner replicates all of it: zeros depend on
    no input, so no sharding propagates to them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)
    pdef = jax.tree_util.tree_structure(params)

    def mirrors_params(x) -> bool:
        return jax.tree_util.tree_structure(x) == pdef

    out = jax.tree_util.tree_map(
        lambda x: shardings if mirrors_params(x)
        else NamedSharding(mesh, P()),
        jax.eval_shape(tx.init, params), is_leaf=mirrors_params)
    return jax.jit(tx.init, out_shardings=out)(params)


class TrainState(NamedTuple):
    """What `build_train_state` returns."""
    params: Any
    opt_state: Any
    # step_fn(params, opt_state, tokens) -> (params, opt_state, loss,
    # counters): dispatches the jitted step (span `train.step.dispatch`)
    # and returns device arrays; params and opt_state are donated
    step_fn: Callable
    model: Any               # the family's training module
    # grads_fn(params, tokens) -> ((loss, (counters, aux)), grads): the
    # jitted value_and_grad of the very loss the step differentiates, at
    # the step's shapes — what a test or a correctness sample reads the
    # gradients of the timed path from
    grads_fn: Callable
    counter_names: Tuple[str, ...]   # of `counters`' entries
    # read(loss, counters) -> (float, {name: int}): one transfer for both
    # (span `train.step.sync`), the counters added to this process's
    # `ray_tpu_train_*` metrics
    read: Callable


def build_train_state(model, mesh, rng_seed: int = 0,
                      learning_rate: float = 3e-4, batch_size: int = 8,
                      seq_len: int = 128,
                      attention_kernel: Optional[Callable] = None
                      ) -> TrainState:
    """Init sharded (params, opt_state) and a jitted adamw train step
    for any model family: `model` is what `LLMEngine(model=...)` takes —
    a family's config instance or a dictionary of published keys with
    its `model_type` — resolved by `ray_tpu.models.resolve`, and the
    family's train side (`train_build`, `train_loss`, `param_rules`;
    models/__init__.py) gives the module, the loss and the layout."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import models
    from ray_tpu.parallel.mesh import init_sharded, shard_batch

    family, cfg = models.resolve(model)
    if attention_kernel is None:
        attention_kernel = _mesh_attention_kernel(mesh)
    module = family.train_build(cfg, attention_kernel)
    names = tuple(getattr(module, "train_counters", ()))
    rng = jax.random.PRNGKey(rng_seed)
    sample = jnp.zeros((batch_size, seq_len), dtype=jnp.int32)

    with mesh:
        params = init_sharded(
            mesh, lambda r: module.init(r, sample)["params"], rng,
            family.param_rules())
        tx = optax.adamw(learning_rate)
        opt_state = _init_opt_state(mesh, tx, params)

        def loss_fn(p, tokens):
            out = module.apply({"params": p}, tokens)
            logits, counters, aux = (
                out if isinstance(out, tuple)
                else (out, jnp.zeros((0,), jnp.int32), ()))
            return family.train_loss(logits, tokens), (counters, aux)

        value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(p, o, tokens):
            (loss, (counters, _)), grads = value_and_grad(p, tokens)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return p, o, loss, counters

        grads_jit = jax.jit(value_and_grad)

    span = jax.profiler.TraceAnnotation

    def step_fn(p, o, tokens):
        with span("train.step.dispatch"):
            tokens = shard_batch(mesh, tokens)
            with mesh:
                return step(p, o, tokens)

    def grads_fn(p, tokens):
        with mesh:
            return grads_jit(p, shard_batch(mesh, tokens))

    def read(loss, counters):
        with span("train.step.sync"):
            loss, counters = jax.device_get((loss, counters))
        counted = {n: int(v) for n, v in zip(names, counters)}
        if counted:
            from ray_tpu._private.metrics import train_step_counters

            for name, value in counted.items():
                train_step_counters(name).inc(value)
        return float(loss), counted

    return TrainState(params, opt_state, step_fn, module, grads_fn, names,
                      read)


def build_llama_train_state(cfg, mesh, rng_seed: int = 0,
                            learning_rate: float = 3e-4,
                            batch_size: int = 8, seq_len: int = 128,
                            attention_kernel: Optional[Callable] = None):
    """`build_train_state` for a LlamaConfig, in the older form.

    Returns (params, opt_state, step_fn, model) where
    step_fn(params, opt_state, tokens) -> (params, opt_state, loss).
    """
    state = build_train_state(cfg, mesh, rng_seed, learning_rate,
                              batch_size, seq_len, attention_kernel)

    four_values = state.step_fn     # not `state`: it holds the trees

    def step_fn(p, o, tokens):
        return four_values(p, o, tokens)[:3]

    return state.params, state.opt_state, step_fn, state.model


def build_llama_stage_state(cfg, mesh, layer_range, *, first: bool,
                            last: bool, microbatch_size: int, seq_len: int,
                            num_microbatches: int, rng_seed: int = 0,
                            learning_rate: float = 3e-4,
                            attention_kernel: Optional[Callable] = None):
    """Init one MPMD pipeline stage: sharded (params, opt_state) on the
    IN-STAGE mesh (fsdp/sp/tp — ``pp`` multiplies this layout instead of
    replacing it) plus the jitted stage functions the 1F1B loop replays.

    Returns a dict:
      params, opt_state           sharded stage subtree + adamw state
      fwd(p, x) -> y              stage forward (None for the last stage,
                                  whose forward fuses into the loss bwd)
      bwd(p, x, gy) -> (gp, gx)   recompute-backward: re-runs the stage
                                  forward inside the vjp (same FLOP trade
                                  as cfg.remat) so only the stage INPUT is
                                  kept resident per in-flight microbatch
      loss_bwd(p, x, tokens) -> (loss, gp[, gx])   last stage only
      opt_step(p, o, acc) -> (p, o)   adamw on accumulated grads / m
      accum(acc, g) -> acc        donating grad accumulator
      zero_grads(p) -> acc        fresh accumulator
      shard_value(x) -> x         device_put a microbatch onto the mesh
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models.llama import (LlamaStage, causal_lm_loss,
                                      llama_param_rules)
    from ray_tpu.parallel.mesh import init_sharded, shard_batch

    if attention_kernel is None:
        attention_kernel = _mesh_attention_kernel(mesh)
    start, end = layer_range
    model = LlamaStage(cfg, start=start, end=end, first=first, last=last,
                       kernel=attention_kernel)
    rng = jax.random.PRNGKey(rng_seed)
    if first:
        sample = jnp.zeros((microbatch_size, seq_len), dtype=jnp.int32)
    else:
        sample = jnp.zeros((microbatch_size, seq_len, cfg.dim),
                           dtype=cfg.dtype)
    scale = 1.0 / float(num_microbatches)
    from functools import partial

    with mesh:
        params = init_sharded(mesh, lambda r: model.init(r, sample)["params"],
                              rng, llama_param_rules())
        tx = optax.adamw(learning_rate)
        opt_state = _init_opt_state(mesh, tx, params)

        def apply_fn(p, x):
            return model.apply({"params": p}, x)

        fwd = None if last else jax.jit(apply_fn)

        loss_bwd = None
        bwd = None
        if last:
            def loss_fn(p, x, tokens):
                return causal_lm_loss(apply_fn(p, x), tokens)

            if first:  # degenerate pp=1 stage: tokens in, no gx out
                @jax.jit
                def loss_bwd(p, x, tokens):
                    loss, gp = jax.value_and_grad(loss_fn)(p, x, tokens)
                    return loss, gp
            else:
                @jax.jit
                def loss_bwd(p, x, tokens):
                    loss, (gp, gx) = jax.value_and_grad(
                        loss_fn, argnums=(0, 1))(p, x, tokens)
                    return loss, gp, gx
        elif first:
            @jax.jit
            def bwd(p, x, gy):
                _, vjp = jax.vjp(lambda p_: apply_fn(p_, x), p)
                (gp,) = vjp(gy)
                return gp, None
        else:
            @jax.jit
            def bwd(p, x, gy):
                _, vjp = jax.vjp(apply_fn, p, x)
                return vjp(gy)

        @partial(jax.jit, donate_argnums=(0,))
        def accum(acc, g):
            return jax.tree_util.tree_map(jnp.add, acc, g)

        zero_grads = jax.jit(
            lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def opt_step(p, o, acc):
            g = jax.tree_util.tree_map(lambda a: a * scale, acc)
            updates, o = tx.update(g, o, p)
            p = optax.apply_updates(p, updates)
            return p, o

    def run_in_mesh(fn):
        def wrapped(*args):
            with mesh:
                return fn(*args)
        return wrapped

    return {
        "params": params, "opt_state": opt_state,
        "fwd": run_in_mesh(fwd) if fwd is not None else None,
        "bwd": run_in_mesh(bwd) if bwd is not None else None,
        "loss_bwd": run_in_mesh(loss_bwd) if loss_bwd is not None else None,
        "opt_step": run_in_mesh(opt_step),
        "accum": run_in_mesh(accum),
        "zero_grads": run_in_mesh(zero_grads),
        "shard_value": lambda x: shard_batch(mesh, x),
        "model": model, "mesh": mesh,
    }


def param_count(params) -> int:
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(params))
