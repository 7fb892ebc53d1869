"""Test env: JAX on a virtual 8-device CPU mesh, so the multi-chip
sharding paths compile and run without TPU hardware.  The persistent
compilation cache is on, as at every entry point
(utils.enable_compilation_cache), so engines that trace the same
program in different tests compile it once.
"""

import contextlib
import os

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from raft_tla_tpu.utils import enable_compilation_cache  # noqa: E402
from raft_tla_tpu.utils import ref_or_local  # noqa: E402,F401

enable_compilation_cache()


@contextlib.contextmanager
def jax_cache_off():
    """JAX's persistent compilation cache off for the block, then back
    as it was (reset_cache drops the once-per-process enabled check).
    For programs that must not be read from or written to it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def no_jax_cache():
    with jax_cache_off():
        yield

# ---------------------------------------------------------------------------
# Shared oracle-reference cache (round-13 suite diet): many files
# compare engines against the SAME (cfg, depth) oracle exploration —
# each Python BFS re-run costs seconds against the 870s tier-1 budget.
# Results are treated as READ-ONLY by every caller (counts /
# level_sizes / violations / kept states are only read).
# ---------------------------------------------------------------------------

_ORACLE_CACHE = {}


def cached_explore(cfg, **kw):
    """spec_of(cfg).oracle_explore(cfg, **kw), memoized per (spec,
    cfg repr, kwargs) for the whole session."""
    from raft_tla_tpu.spec import spec_of
    ir = spec_of(cfg)
    key = (ir.name, repr(cfg), tuple(sorted(kw.items())))
    if key not in _ORACLE_CACHE:
        _ORACLE_CACHE[key] = ir.oracle_explore(cfg, **kw)
    return _ORACLE_CACHE[key]
