"""Shape-portable resume images: engine-agnostic extraction of a
checkpoint's BFS wavefront (ROADMAP item-2 elastic prerequisite).

Both checkpoint formats — the classic ``Engine``'s (which
``PjitShardedEngine`` also writes, gathered to host) and
``SpillEngine``'s — carry the same logical wavefront under different
physical layouts: the visited-fingerprint SET, the frontier rows (the
last committed level) in gid order, the run counters, and the trace
archives.  This module reads either into one normalized
``PortableImage``:

- ``keys``  — [N, W] u32 visited fingerprints (dense tables are
  sparsified; host-partition images are pooled — membership is a set
  property, so the physical slot layout never matters);
- ``rows``/``gids``/``con`` — frontier rows batch-major in narrow
  storage dtypes, their global ids, and the constraint mask
  (prune-not-expand: ``con=False`` rows are archived but never
  expanded);
- counters (``CheckResult``), depth, ``n_states``, and the archives.

A target engine re-partitions on load: the spill engine rebuilds its
table image (and host partitions) from the key set, the classic and
pjit engines re-insert it into their table (the pjit mesh then
re-partitions it onto its named shardings, so ANY device count
works).  That is what makes a pjit-mesh checkpoint resumable on a
different pod-slice shape or on the spill engine after a lost
machine.

Exactness: dedup needs key-set MEMBERSHIP, not slot layout, and gid
assignment for new states is discovery-order determined by the
frontier row order this image preserves — so a portable resume lands
on the exact counts / level sizes of an uninterrupted run (each
engine is oracle-exact, and the pjit engine runs the classic engine's
program on any mesh size).

The removed shard_map mesh engines' format (meta ``sharded``) is
refused by name (engine/bfs.ckpt_refuse_removed_format).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from .ckpt_chain import IntegrityError, open_validated

U32 = np.uint32(0xFFFFFFFF)


@dataclass
class PortableImage:
    spec: str
    cfg_repr: str
    depth: int
    n_states: int
    res: object                       # engine.bfs.CheckResult
    keys: np.ndarray                  # [N, W] u32 visited fingerprints
    rows: Dict[str, np.ndarray]       # frontier, batch-major narrow
    gids: np.ndarray                  # [F] int32
    con: np.ndarray                   # [F] bool (expandable mask)
    store_states: bool
    # in-RAM trace archives (parents/lanes/state blocks per level), or
    # a disk-archive reference the target reattaches
    parents: List[np.ndarray] = field(default_factory=list)
    lanes: List[np.ndarray] = field(default_factory=list)
    states: List[Dict[str, np.ndarray]] = field(default_factory=list)
    disk_archive_levels: Optional[int] = None
    source_format: str = "engine"
    source_path: str = ""

    @property
    def W(self) -> int:
        return int(self.keys.shape[1])

    @property
    def n_vis(self) -> int:
        return int(self.keys.shape[0])

    @property
    def n_front(self) -> int:
        return int(self.gids.shape[0])

    def fresh_result(self):
        """A fresh CheckResult copy of the image's counters.  Resume
        consumers MUST continue on a copy — an image can seed several
        engines (the portable-resume tests fan one checkpoint out to
        multiple targets), and counters are mutated in place."""
        from ..engine.bfs import CheckResult
        r = self.res
        out = CheckResult(
            distinct_states=r.distinct_states,
            generated_states=r.generated_states, depth=r.depth,
            level_sizes=list(r.level_sizes),
            overflow_faults=r.overflow_faults,
            violations_global=r.violations_global,
            pin_interior_states=r.pin_interior_states,
            levels_fused=r.levels_fused,
            burst_dispatches=r.burst_dispatches,
            burst_bailouts=r.burst_bailouts)
        out.violations = list(r.violations)
        return out

    def expandable(self):
        """(rows, gids) with pruned rows dropped — the spill engines'
        frontier convention (prune-not-expand runs host-side there)."""
        if self.con.all():
            return self.rows, self.gids
        keep = np.nonzero(self.con)[0]
        return ({k: np.ascontiguousarray(v[keep])
                 for k, v in self.rows.items()}, self.gids[keep])


def validate_image(img: "PortableImage", spec_name: str,
                   cfg_repr: str, W: int):
    """The target-engine compatibility gate every ``resume_image``
    consumer runs: same spec, byte-identical config repr (the
    checkpoint-compat identity string), same fingerprint width.
    Raises ``CheckpointError`` with the engines' message style."""
    from ..engine.bfs import CheckpointError
    if not isinstance(img, PortableImage):
        raise CheckpointError(
            f"resume_image must be a resil.portable.PortableImage "
            f"(got {type(img).__name__}) — build one with "
            "load_portable_image(path)")
    if img.spec != spec_name:
        raise CheckpointError(
            f"portable image was written for spec {img.spec!r}; "
            f"engine is running spec {spec_name!r}")
    if img.cfg_repr != cfg_repr:
        raise CheckpointError(
            "portable image was written for a different model "
            f"config:\n  image:  {img.cfg_repr}\n"
            f"  engine: {cfg_repr}")
    if img.W != W:
        raise CheckpointError(
            f"portable image has {img.W} fingerprint streams; engine "
            f"expects {W} (fp64 vs fp128 mismatch)")


def _dense_table_keys(words: List[np.ndarray]) -> np.ndarray:
    """[W] x u32[...C] dense open-addressing table -> [N, W] keys
    (all-ones aliases "empty" — the engines' accepted-risk class)."""
    occ = ~(words[0] == U32)
    for w in words[1:]:
        occ &= ~(w == U32)
    occ = occ if occ.ndim == 1 else occ.reshape(-1)
    flat = [w.reshape(-1) for w in words]
    idx = np.nonzero(occ)[0]
    return np.stack([w[idx] for w in flat], axis=1)


def _in_ram_archives(z, meta):
    n_levels = int(meta.get("n_levels", 0))
    if not (meta.get("store_states") and n_levels >= 0):
        return [], [], []
    st_keys = sorted({nm.split("|", 2)[2] for nm in z.files
                      if nm.startswith("states|0|")})
    parents = [np.asarray(z[f"parents|{i}"]) for i in range(n_levels)]
    lanes = [np.asarray(z[f"lanes|{i}"]) for i in range(n_levels)]
    states = [{k: np.asarray(z[f"states|{i}|{k}"]) for k in st_keys}
              for i in range(n_levels)]
    return parents, lanes, states


def load_portable_image(path: str) -> PortableImage:
    """Read any engine family's checkpoint into a PortableImage.
    Integrity-validated with chain fallback (resil/ckpt_chain), like
    every native resume.  Raises ``CheckpointError`` on unusable
    files."""
    import json

    from ..engine.bfs import (CheckpointError, ckpt_check_bag_order,
                              ckpt_refuse_removed_format, ckpt_result)
    from .ckpt_chain import load_engine_npz
    try:
        z, used = open_validated(path, load_engine_npz)
    except IntegrityError as e:
        raise CheckpointError(str(e)) from e
    meta = json.loads(str(z["meta"]))
    ckpt_refuse_removed_format(used, meta)
    ckpt_check_bag_order(used, meta)
    try:
        if meta.get("spill"):
            img = _extract_spill(z, meta)
        else:
            img = _extract_engine(z, meta)
    except KeyError as e:
        raise CheckpointError(
            f"{used}: checkpoint lacks record {e} — written by an "
            "incompatible engine version; portable resume needs a "
            "round-12+ checkpoint for this engine family") from e
    img.res = ckpt_result(z, meta)
    img.depth = int(meta["depth"])
    img.n_states = int(meta["n_states"])
    img.spec = meta.get("spec", "raft")
    img.cfg_repr = meta["cfg"]
    img.store_states = bool(meta.get("store_states"))
    img.source_path = used
    if meta.get("disk_archive"):
        img.disk_archive_levels = int(meta["arch_levels"])
    else:
        img.parents, img.lanes, img.states = _in_ram_archives(z, meta)
    z.close()
    return img


def _blank(fmt) -> PortableImage:
    return PortableImage(spec="", cfg_repr="", depth=0, n_states=0,
                         res=None, keys=np.zeros((0, 2), np.uint32),
                         rows={}, gids=np.zeros((0,), np.int32),
                         con=np.zeros((0,), bool), store_states=False,
                         source_format=fmt)


def _extract_engine(z, meta) -> PortableImage:
    img = _blank("engine")
    words = []
    w = 0
    while f"carry|vis|{w}" in z:
        words.append(np.asarray(z[f"carry|vis|{w}"]))
        w += 1
    if not words:
        raise KeyError("carry|vis|0")
    img.keys = _dense_table_keys(words)
    n_front = int(meta["n_front"])
    pg_off = int(np.asarray(z["carry|pg_off"]))
    fmask = np.asarray(z["carry|fmask"])[:n_front]
    rows = {}
    for nm in z.files:
        if nm.startswith("carry|front|"):
            k = nm.split("|", 2)[2]
            v = np.asarray(z[nm])          # batch-LAST [..., LCAP]
            rows[k] = np.ascontiguousarray(
                np.moveaxis(v[..., :n_front], -1, 0))
    img.rows = rows
    img.gids = pg_off + np.arange(n_front, dtype=np.int32)
    img.con = fmask.astype(bool)
    return img


def _extract_spill(z, meta) -> PortableImage:
    img = _blank("spill")
    if meta.get("host_table"):
        # the host partitions are the authoritative visited set (the
        # device table is a bounded cache ⊆ them)
        shape = np.asarray(z["carry|hpt|shape"])
        P = int(shape[0])
        parts = [np.asarray(z[f"carry|hpt|keys{p}"]).T
                 for p in range(P)]            # [n_p, W]
        img.keys = (np.concatenate(parts) if parts
                    else np.asarray(z["carry|vis_keys"]).T)
    else:
        img.keys = np.ascontiguousarray(
            np.asarray(z["carry|vis_keys"]).T)
    rows_b, gids_b = [], []
    for i in range(int(meta["n_fblk"])):
        g = np.asarray(z[f"carry|fblk|{i}|g"])
        blk = {}
        for nm in z.files:
            pre = f"carry|fblk|{i}|r|"
            if nm.startswith(pre):
                v = np.asarray(z[nm])          # batch-LAST [..., n]
                blk[nm[len(pre):]] = np.ascontiguousarray(
                    np.moveaxis(v, -1, 0))
        rows_b.append(blk)
        gids_b.append(g.astype(np.int32))
    if rows_b:
        keys0 = rows_b[0].keys()
        img.rows = {k: np.concatenate([r[k] for r in rows_b])
                    for k in keys0}
        img.gids = np.concatenate(gids_b)
    img.con = np.ones((img.gids.shape[0],), bool)
    return img

