"""Worker process for tests/test_pjit.py: one controller of a
multi-controller PjitShardedEngine run (2 procs × N virtual CPU
devices, gloo collectives = the DCN stand-in).  The whole BFS state
lives under NamedShardings on the process-spanning mesh; both
controllers must land on the oracle's exact counts — and on
bit-identical witness traces, since the pjit program IS the classic
engine's program.

Usage: python tools/pjit_worker.py <pid> <nproc> <port> [opts-json]
opts (all optional): {"max_depth": int, "chunk": int, "lcap": int,
                      "vcap": int, "invariants": [names],
                      "store_states": bool, "trace_gid": int,
                      "checkpoint": path, "resume": path,
                      "resume_portable": path,
                      "stop_on_violation": bool}
resume_portable — a checkpoint path loaded through
resil.portable.load_portable_image and re-partitioned onto this mesh
(the round-12 contract: a classic/spill checkpoint resumes at pod
shape).  Caller must set
XLA_FLAGS=--xla_force_host_platform_device_count=N and
JAX_PLATFORMS=cpu before the interpreter starts.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
opts = json.loads(sys.argv[4]) if len(sys.argv) > 4 else {}

import jax  # noqa: E402
jax.config.update("jax_platforms", "cpu")

from raft_tla_tpu.parallel.multihost import init_distributed  # noqa: E402

init_distributed(f"127.0.0.1:{port}", num_processes=nproc,
                 process_id=pid)

# AFTER distributed init: importing the engine initializes XLA
from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine  # noqa: E402
from raft_tla_tpu.config import NEXT_ASYNC, Bounds, ModelConfig  # noqa: E402

cfg = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
    invariants=tuple(opts.get("invariants", ())),
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))

D = len(jax.devices())
store = bool(opts.get("store_states") or opts.get("trace_gid")
             is not None)
eng = PjitShardedEngine(cfg, chunk=opts.get("chunk", 16 * D),
                        lcap=opts.get("lcap", 1 << 12),
                        vcap=opts.get("vcap", 1 << 15),
                        store_states=store)
resume_image = None
if opts.get("resume_portable"):
    from raft_tla_tpu.resil.portable import load_portable_image
    resume_image = load_portable_image(opts["resume_portable"])
r = eng.check(max_depth=opts.get("max_depth", 10 ** 9),
              checkpoint_path=opts.get("checkpoint"),
              resume_from=opts.get("resume"),
              resume_image=resume_image,
              stop_on_violation=opts.get("stop_on_violation", False))
trace = None
if opts.get("trace_gid") is not None:
    # archives are controller-replicated under the pjit gather fns, so
    # EVERY controller can replay any witness chain
    trace = [lbl for lbl, _ in eng.trace(int(opts["trace_gid"]))]
print("RESULT " + json.dumps(dict(
    pid=pid, n_devices=D,
    distinct=int(r.distinct_states), depth=int(r.depth),
    generated=int(r.generated_states),
    level_sizes=[int(x) for x in r.level_sizes],
    violations=int(r.violations_global),
    trace=trace)), flush=True)
