"""Multi-host (DCN) bring-up for the pjit engine (SURVEY §2.14, §7.2
L7: "then multi-host over DCN").

The reference's engine-level counterpart is TLC's multi-worker BFS run
as distributed TLC; here ``parallel/pjit_mesh.PjitShardedEngine``'s
named-sharding mesh simply spans every host's chips: one controller
process per host runs the same jitted program (multi-controller SPMD),
and the dedup exchange and the replicated per-level outputs ride ICI
inside a host and DCN across hosts.

Bring-up:

    # on every host (coordinator = host 0), BEFORE any jax use:
    from raft_tla_tpu.parallel.multihost import init_distributed
    init_distributed("host0:9911", num_processes=4, process_id=rank)
    eng = PjitShardedEngine(cfg, chunk=1024, lcap=..., vcap=...)
    res = eng.check()   # counts, archives and traces identical on
                        # every controller

Verified in-repo by tests/test_pjit.py: two controller processes x two
virtual CPU devices each (gloo collectives — the CPU stand-in for DCN,
driven by tools/pjit_worker.py) land on oracle-identical counts.
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
