"""Spec-agnostic frontend: the ``SpecIR`` contract (ROADMAP item 2).

The engines (bfs / spill / pjit_mesh / sim) never execute
TLA+; they consume a *compiled operator surface*:

  * an Init-state constructor and a bit-packed SoA layout + codec
    (encode / decode / narrow / widen),
  * a registry of vmapped action *families* — each with its parameter
    grid, its successor kernel, AND its guard-algebra declaration (the
    signed-weight/threshold row the int8 guard-matmul of PR 8 compiles;
    a family without one fails loudly at Expander construction),
  * per-family enabled-lane density caps (buffer sizing),
  * invariant / constraint / scenario-property registries (device
    predicates) and their plain-Python oracle twins,
  * a symmetry-canonical fingerprinter and the oracle's symmetry group,
  * the oracle explorer the differential harness pins everything to.

``SpecIR`` bundles exactly that surface.  Everything Raft-specific that
used to be reached via direct ``models.raft`` / ``ops.*`` imports now
routes through the IR handle (``spec_of(cfg)``), so a second spec is a
data change, not an engine fork — ``spec/paxos`` is the proof tenant
(single-decree + multi-instance Paxos; PAPERS.md: arXiv:2004.05074
argues the two specs are near-isomorphic, arXiv:1905.10786 gives the
action mapping).

Config dispatch: every model config object carries a ``spec`` class
attribute naming its IR (``ModelConfig.spec == "raft"``,
``PaxosConfig.spec == "paxos"``).  It is a class attribute, not a
dataclass field, so ``repr(cfg)`` — the checkpoint-compat key — is
unchanged for every existing Raft checkpoint; the spec name is
additionally stamped into checkpoint meta / ``--stats-json`` / the obs
ledger, and resume refuses on a spec mismatch before the cfg repr is
even compared.

The SoA *ctr* contract is shared across specs: every spec's encoded
state carries a ``ctr`` int32[NCTR] lane vector with ``C_GLOBLEN``
(history length) and ``C_OVERFLOW`` (un-representability fault) at the
indices below — the engines' harvest loops and depth gates read only
these two, so they stay spec-blind.  (ops/codec re-exports them for the
historical import path.)
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field
from typing import Callable, Dict, Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# The shared ctr-lane contract (see module docstring).  ops/codec.py
# aliases these; spec/paxos/codec.py builds its ctr vector against them.
# ---------------------------------------------------------------------------

NCTR = 8
C_NLEADERS, C_NREQ, C_NTRIED, C_NMC, C_GLOBLEN, C_OVERFLOW = range(6)


@dataclass(frozen=True)
class SpecIR:
    """One spec's compiled operator surface (see module docstring).

    All members are plain callables/tables so an IR is constructed
    without importing JAX-heavy modules until the engines actually use
    it; the registry below builds each IR lazily and caches it.
    """

    name: str
    version: int                      # bumped on IR-structure changes

    # ---- packed layout + codec ----------------------------------------
    make_layout: Callable             # cfg -> layout object
    init_state: Callable              # cfg -> (sv, hist) oracle pair
    encode: Callable                  # (lay, sv, hist) -> SoA dict
    decode: Callable                  # (lay, arrs) -> (sv, hist)
    narrow: Callable                  # (lay, arrs) -> storage dtypes
    widen: Callable                   # arrs -> kernel dtypes
    view_keys: Tuple[str, ...]        # state-identity arrays
    nonview_keys: Tuple[str, ...]     # history/feature arrays
    state_to_obj: Callable            # (sv, hist) -> JSON-able dict
    state_from_obj: Callable          # dict -> (sv, hist)

    # ---- kernels + families -------------------------------------------
    make_kernels: Callable            # lay -> kernels object (.derived,
    #                                   .guard_features, .guard_feature_offsets)
    build_families: Callable          # lay -> List[engine.expand.Family]
    family_density: Mapping[str, int]  # per-family enabled-lane density

    # ---- predicates ----------------------------------------------------
    make_predicates: Callable         # lay -> device predicate object
    #                                   (.invariant_fn/.constraint_fn/.action_fn)
    scenario_properties: Tuple[str, ...]
    known_invariants: frozenset
    known_constraints: frozenset
    known_action_constraints: frozenset
    # invariants/constraints whose ORACLE form scans history records a
    # device-emitted seed cannot carry (cli seed-trace guard)
    glob_dependent: frozenset = frozenset()

    # ---- identity ------------------------------------------------------
    # make_fingerprinter receives the RESOLVED sym_canon mode ("sort" |
    # "minperm") from engine/fingerprint.Fingerprinter (round 15).
    make_fingerprinter: Callable = None   # (cfg, sym_canon) -> fingerprinter
    symmetry_perms: Callable = None       # cfg -> [perm tuples]
    # orbit-sort signature kernel (round 15): (fingerprinter, svT,
    # prep) -> u32[S, B] permutation-EQUIVARIANT per-server signature
    # (sig(relabel(s,σ))[σ(i)] == sig(s)[i]); svT is batch-last, prep
    # is the fingerprinter's own spec-defined precompute object.
    # Signature strength is performance-only — the certificate +
    # min-over-perms fallback in the fingerprinter pins correctness.
    server_signature: Callable = None

    # ---- oracle twins (the differential anchor) ------------------------
    oracle_explore: Callable = None       # explore(cfg, **kw)
    oracle_successors: Callable = None    # (sv, h, cfg) -> [(lbl, sv, h)]
    oracle_walk_key: Callable = None      # sv -> hashable identity key

    # ---- optional hooks ------------------------------------------------
    prefix_pin_seeds: Optional[Callable] = None   # cfg -> (seeds, interiors)
    sim_progress: Optional[Callable] = None       # (kern, lay) -> (svT -> [W])
    default_config: Optional[Callable] = None     # () -> a small cfg
    # serving-layer bucket ceiling (serve/batch): cfg -> (ceiling cfg,
    # bucket param dict).  Jobs whose ceiling cfg + params match batch
    # into ONE job-vmapped device program; the ceiling is the config
    # the bucket engine compiles.  Round 13: the ceiling may now be
    # STRICTLY ABOVE the job's config — value-like bounds (MaxTerm
    # etc., paxos ballots/values/instances) pad up to a rung ladder
    # (``pad_rung``) so heterogeneous small configs share one
    # AOT-compiled program — provided the spec also supplies
    # ``serve_runtime`` below to restore the job's exact semantics.
    serve_bucket: Optional[Callable] = None
    # (expander, job cfg) -> the job's runtime-thresholds data under
    # the bucket's CEILING expander: {"thr": int32 [A] guard
    # thresholds, "mask": bool [A] family lane mask, "bounds": int32
    # [NB] search-bounds vector} (host numpy; serve/batch stacks a
    # leading [J] axis and the batched burst vmaps them as device
    # data).  The contract that makes a padded ceiling EXACT: masked
    # lanes never generate candidates, so the surviving stream is the
    # job's own enumeration order, and every Bounded*-style constraint
    # reads the job's own bound from the vector.  None = ceilings are
    # always exact for this spec (the pre-round-13 contract).
    serve_runtime: Optional[Callable] = None

    @property
    def all_keys(self) -> Tuple[str, ...]:
        return self.view_keys + self.nonview_keys

    def fingerprint(self) -> str:
        """Short stable hash of the IR *structure* (not of any run
        config): stamped into ``--stats-json``, the obs ledger and
        checkpoint meta so a resumed/compared run records which
        frontend compiled it."""
        desc = json.dumps([
            self.name, self.version,
            sorted((k, int(v)) for k, v in
                   dict(self.family_density).items()),
            list(self.scenario_properties),
            sorted(self.known_invariants),
            sorted(self.known_constraints),
            sorted(self.known_action_constraints),
            list(self.view_keys), list(self.nonview_keys),
        ], separators=(",", ":"))
        return hashlib.sha256(desc.encode()).hexdigest()[:12]


def pad_rung(v: int, floor: int = 1) -> int:
    """The serving ceiling ladder: round a value-like bound up to the
    next power of two, never below ``floor``.  Shared by every spec's
    ``serve_bucket`` so two tenants' independently-computed ceilings
    agree whenever their bounds share a rung — that agreement IS the
    bucket hit.  Coarser rungs (a higher floor) = more sharing but
    bigger padded layouts; powers of two keep the worst-case pad at
    2x above the floor.  Each spec picks its floor by what padding
    costs it: raft bounds only widen bit-packing fields (floor 4 —
    the whole small-serving range shares one rung), while paxos
    ballots/values/instances multiply the message universe and the
    lane grid (floor 2)."""
    v = max(int(v), int(floor))
    if v <= 1:
        return max(v, 0)
    return 1 << (v - 1).bit_length()


# ---------------------------------------------------------------------------
# Registry.  Builders are lazy (each imports its spec's modules on first
# use) and cached; unknown names fail with the known-spec list — the
# error every CLI/engine entry point surfaces verbatim.
# ---------------------------------------------------------------------------

def _build_raft() -> SpecIR:
    from .raft_ir import build_ir
    return build_ir()


def _build_paxos() -> SpecIR:
    from .paxos.ir import build_ir
    return build_ir()


_BUILDERS: Dict[str, Callable[[], SpecIR]] = {
    "raft": _build_raft,
    "paxos": _build_paxos,
}

_CACHE: Dict[str, SpecIR] = {}


def spec_names() -> Tuple[str, ...]:
    return tuple(sorted(_BUILDERS))


def get_spec(name: str) -> SpecIR:
    if name not in _BUILDERS:
        raise ValueError(
            f"unknown spec {name!r}; known specs: "
            f"{', '.join(spec_names())}")
    ir = _CACHE.get(name)
    if ir is None:
        ir = _CACHE[name] = _BUILDERS[name]()
        assert ir.name == name, (ir.name, name)
    return ir


def spec_of(cfg) -> SpecIR:
    """The IR handle for a model config (``cfg.spec`` class attribute;
    absent attribute reads as the raft frontend — every pre-IR config
    object is a Raft one)."""
    return get_spec(getattr(cfg, "spec", "raft"))
