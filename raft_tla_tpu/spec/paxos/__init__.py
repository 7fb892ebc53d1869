"""Paxos — the second ``SpecIR`` tenant (single-decree + multi-instance).

The proof that the frontend is real: the engines run this spec
UNMODIFIED, differentially pinned against the plain-Python oracle in
``model.py`` exactly like Raft is pinned against ``models/raft.py``.
See ``ir.py`` for the operator-surface assembly and ``model.py`` for
the semantics source of truth.
"""

from .config import PaxosConfig  # noqa: F401
