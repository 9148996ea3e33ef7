"""Child-process spawning for daemons and workers.

Children start the way this installation starts Python — same
interpreter, site enabled — with the checkout on PYTHONPATH so that
`-m ray_tpu...` resolves whatever the child's working directory.
Control-plane daemons never import jax.  Workers import it in the first
task that uses it, by which time the lease has set the chip environment
(worker._apply_chip_env); where the compile cache goes is decided here,
through the environment, so that no control-plane process imports jax
to configure it.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Tuple


# Pre-bound at import time: preexec_fn runs between fork and exec, where
# imports/dlopen may deadlock if another thread held a lock at fork.
_PR_SET_PDEATHSIG = 1
_SIGKILL = 9
try:
    import ctypes as _ctypes

    _libc = _ctypes.CDLL("libc.so.6", use_errno=True)
except Exception:
    _libc = None


def set_pdeathsig():
    """preexec_fn: deliver SIGKILL to the child when its parent dies, so
    killing a node agent takes its workers down with it (real node-death
    semantics for fault-injection tests; Linux only).  Only calls the
    pre-bound libc.prctl — no imports or allocation post-fork."""
    if _libc is not None:
        _libc.prctl(_PR_SET_PDEATHSIG, _SIGKILL)


def repo_root() -> str:
    import ray_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))


def python_module_cmd(module: str, argv: List[str] = ()) -> Tuple[List[str], Dict[str, str]]:
    """Returns (cmd, env_updates) to run `python -m module`."""
    paths = [repo_root()]
    existing = os.environ.get("PYTHONPATH", "")
    if existing:
        paths.append(existing)
    env = {"PYTHONPATH": os.pathsep.join(dict.fromkeys(paths))}
    return [sys.executable, "-m", module, *argv], env


def compile_cache_env() -> Dict[str, str]:
    """Environment that places jax's persistent compilation cache for a
    process about to start jax work.  A JAX_COMPILATION_CACHE_DIR set
    from outside is left alone.  Otherwise: one fixed directory inside
    the checkout (git-ignored) — the path is part of the cache key, so
    it is never built from a temp name, a pid or a time."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return {}
    return {"JAX_COMPILATION_CACHE_DIR":
            os.path.join(repo_root(), ".jax_compile_cache")}
