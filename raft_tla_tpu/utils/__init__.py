"""Shared host-side helpers used across the engines.

Device-side math lives in ops/ and engine/fingerprint; these are the
small numpy/python twins the BFS drivers share (engine/bfs re-exports
them under its historical names for backward compatibility).
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; every entry point
    (cli.main, bench.py, chip_smoke.py, __graft_entry__, the tools/
    mains) calls this before its first jit; library code never does.  ``$JAX_COMPILATION_CACHE_DIR`` wins when set
    (JAX reads it itself); otherwise the fixed ``<repo>/.jax_cache``.
    Returns the directory in use."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # persist every program: the root path compiles many small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def ref_or_local(path: str) -> str:
    """A reference model path (/root/reference/...), falling back to
    the repo-local twin under configs/ when the reference tree is not
    on this machine (tests/test_sim.py pins that the twin parses
    identically).  The twins carry only the cfg + the bound-constant
    stub the parser scans, not the full spec text."""
    if os.path.exists(path):
        return path
    local = os.path.join(REPO_ROOT, "configs",
                         os.path.relpath(path, "/root/reference"))
    return local if os.path.exists(local) else path


# the probe-walk contract every visited-table image shares (device
# tables in engine/bfs + engine/spill, host partitions in
# engine/host_table): home slot = fmix32-fold of the key streams
# seeded with this salt.  ONE definition — a drifted twin would walk
# different probe chains on host vs device and silently inflate
# distinct counts.
HOME_SALT = 0x9E3779B9


def fmix32_int(x: int) -> int:
    """Host twin of engine.fingerprint.fmix32 (murmur3 finalizer) on
    plain ints — used for host-side probe placement of root/seed keys."""
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def fmix32_np(x: np.ndarray) -> np.ndarray:
    """Vectorized numpy twin of the same finalizer — host-side image
    building/probing over whole key arrays (engine/host_table)."""
    x = x.astype(np.uint32, copy=True)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def cat_arrays(chunks: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Concatenate a list of SoA dicts along the batch axis."""
    return {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}


def take_arrays(arrs: Dict[str, np.ndarray], idx) -> Dict[str, np.ndarray]:
    """Row-select every array of an SoA dict."""
    return {k: v[idx] for k, v in arrs.items()}


def combine_u64(fp: np.ndarray) -> np.ndarray:
    """[N, n_streams] u32 -> [N, n_streams//2] u64 words (a single u64
    column for the default 2-stream mode) — the canonical bit layout of
    the dedup key (engine.fingerprint re-exports this)."""
    fp = np.asarray(fp, dtype=np.uint64)
    return (fp[:, 0::2] << np.uint64(32)) | fp[:, 1::2]


def fp_key(fp_u32: np.ndarray) -> np.ndarray:
    """[N, n_streams] u32 -> 1-D sortable dedup key covering ALL streams:
    plain u64 for the 2-stream default, a lexicographic structured array
    for fp128 (so the extra streams actually buy collision resistance)."""
    u64 = combine_u64(fp_u32)
    if u64.shape[1] == 1:
        return u64[:, 0]
    dtype = np.dtype([(f"w{i}", "<u8") for i in range(u64.shape[1])])
    return np.ascontiguousarray(u64).view(dtype)[:, 0]
