"""The plain reference (bench/reference/plain_bfs.cc): a single-threaded
breadth-first search written from the spec, with an exact set of whole
canonical states.  It is built with g++ into the checkout's cache
directory and driven from a configuration's JSON.

It imports nothing of the program and takes nothing the program made:
every argument it gets is read from bench/configs/<config>.json.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import time
from dataclasses import dataclass, field
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "reference", "plain_bfs.cc")
BOUNDS = ("max_log_length", "max_restarts", "max_timeouts", "max_terms",
          "max_client_requests", "max_membership_changes",
          "max_tried_membership_changes")


@dataclass
class RefResult:
    distinct: int
    generated: int
    depth: int
    level_sizes: List[int]
    violated: List[str] = field(default_factory=list)
    seconds: float = 0.0


def build(cache_dir: str) -> str:
    """Compile the reference once per checkout, keyed on its content."""
    with open(SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    os.makedirs(cache_dir, exist_ok=True)
    exe = os.path.join(cache_dir, f"plain_bfs.{digest}")
    if os.path.exists(exe):
        return exe
    tmp = f"{exe}.tmp{os.getpid()}"
    subprocess.run(["g++", "-O2", "-std=c++17", "-o", tmp, SRC],
                   check=True, capture_output=True, text=True)
    os.replace(tmp, exe)
    return exe


def arguments(model: dict, max_depth: int, fp_bits: int = 0) -> List[str]:
    """The reference's key=value arguments for ``model`` (a config
    JSON's "model"); invariants go by the form the spec checks."""
    b = model["bounds"]
    forms = model.get("invariant_forms", {})
    ints = lambda xs: ",".join(str(x) for x in xs)  # noqa: E731
    args = {
        "servers": model["servers"],
        "init_servers": ints(model["init_servers"]),
        "values": ints(model["values"]),
        "next": model["next"],
        "num_rounds": model["num_rounds"],
        "symmetry": int(model["symmetry"]),
        "max_inflight_messages": model["max_inflight_messages"],
        "constraints": ",".join(model["constraints"]),
        "invariants": ",".join(forms.get(nm, nm)
                               for nm in model["invariants"]),
        "max_depth": max_depth,
        "fp_bits": fp_bits,
        **{k: b[k] for k in BOUNDS},
    }
    return [f"{k}={v}" for k, v in args.items()]


def check(exe: str, model: dict, max_depth: int,
          fp_bits: int = 0) -> RefResult:
    """One whole check of ``model`` to ``max_depth``.  ``fp_bits`` > 0
    is the control: a lossy dedup key of that many bits."""
    t0 = time.perf_counter()
    p = subprocess.run([exe, *arguments(model, max_depth, fp_bits)],
                       capture_output=True, text=True, check=True)
    secs = time.perf_counter() - t0
    out = json.loads(p.stdout.strip().splitlines()[-1])
    forms = model.get("invariant_forms", {})
    back = {forms.get(nm, nm): nm for nm in model["invariants"]}
    return RefResult(distinct=out["distinct"], generated=out["generated"],
                     depth=out["depth"], level_sizes=out["level_sizes"],
                     violated=sorted(back[nm] for nm in out["violated"]),
                     seconds=secs)
