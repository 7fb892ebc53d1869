"""Multi-host (DCN) scale-out for the sharded BFS (SURVEY §2.14, §7.2
L7: "then multi-host over DCN").

The reference's engine-level counterpart is TLC's multi-worker BFS run
as distributed TLC; here the ShardedEngine's hash-ownership mesh simply
spans every host's chips: one controller process per host calls the
same jit'd shard_map program (multi-controller SPMD), the all_to_all
candidate exchange and the replicated per-level scalar matrix ride ICI
inside a host and DCN across hosts, and each controller only ever
touches its own addressable shards (mesh.py's `local_rows` /
replicated-scal design).

Bring-up:

    # on every host (coordinator = host 0), BEFORE any jax use:
    from raft_tla_tpu.parallel.multihost import init_distributed
    init_distributed("host0:9911", num_processes=4, process_id=rank)
    eng = MultiHostEngine(cfg, chunk=1024, lcap=..., vcap=...)
    res = eng.check()   # counts + violations_global identical on every
                        # host; res.violations holds only THIS host's
                        # shard-local decoded violations

Verified in-repo by tests/test_multihost.py: two controller processes
x two virtual CPU devices each (gloo collectives — the CPU stand-in
for DCN) land on oracle-identical counts.

Constraints vs the single-host ShardedEngine:
- `store_states=True` needs `trace_dir=` — a directory every
  controller can reach (TLC's distributed workers write worker-local
  ``states/`` files to shared storage the same way).  Each controller
  archives its own device shards per level; ``trace()`` on any
  controller merges the per-controller files device-major (the global
  id order) and replays the full witness chain, so a violation found
  at mesh scale has a trace without a single-host re-run
  (tests/test_multihost.py::test_multihost_violation_trace).  Without
  a trace_dir, violations still print decoded states shard-locally
  (``Violation.state``).  store_states composes with checkpointing
  (round 14): every controller's checkpoint shard carries its own
  archive rows + device segmentation, so a resumed run's final
  trace_dir merge equals an uninterrupted run's bit-exact.
- Level/send/compaction capacities (lcap/fcap/scap) GROW mid-run like
  the single-host engine's: every controller takes the identical
  growth branch from the replicated scalar matrix and re-homes its
  shards into identically-shaped new global arrays in lockstep
  (mesh.py `_grow_sharded` runs as SPMD ops on the P("d") arrays).
  The visited table grows the same way (`_rehash_sharded`).  Proven
  under 2 controllers by
  tests/test_multihost.py::test_multihost_midrun_growth — pre-sizing
  is a performance choice (growth replays the level), not a limit.
"""

from __future__ import annotations


import jax

def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int):
    """Initialize the JAX distributed runtime for a multi-controller
    run.  On CPU (tests / DCN rehearsal) also selects the gloo
    collectives backend; for multiple virtual CPU devices per process
    the caller must have set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before JAX
    initialized its backends.  The gloo setting only affects the CPU
    backend; TPU collectives are native."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)




def __getattr__(name):
    # lazy: importing the engine initializes the XLA backend, which
    # must happen AFTER jax.distributed.initialize / init_distributed
    if name == "MultiHostEngine":
        from .multihost_engine import MultiHostEngine
        return MultiHostEngine
    raise AttributeError(name)
