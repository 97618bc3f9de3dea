"""hypermerge_tpu — a TPU-native peer-to-peer CRDT document framework.

A ground-up re-design of the capabilities of hypermerge (reference:
/root/reference, a Node/TypeScript library combining an Automerge-style JSON
CRDT with hypercore-style signed append-only feeds) built TPU-first:

- The CRDT compute path — vector-clock algebra, LWW map resolution, RGA list
  ordering, whole-document materialization — runs as batched JAX/XLA programs
  (`vmap` across documents; whole slabs dealt round-robin across the
  visible chips, `parallel/sharded.SlabRoundRobin`).
- The runtime around it — repo orchestration, per-actor append-only signed
  feeds, replication, storage — is host-side Python/C++ mirroring the
  reference's layer map (see SURVEY.md §1).

Public surface mirrors the reference facade (reference src/index.ts:1-12,
src/Repo.ts:16-34): Repo, Handle, RepoFrontend, RepoBackend, DocFrontend,
DocBackend plus document types.
"""

__version__ = "0.1.0"

from .utils.ids import (  # noqa: F401
    ActorId,
    DocId,
    DocUrl,
    HyperfileId,
    HyperfileUrl,
    RepoId,
    to_doc_url,
    to_hyperfile_url,
    url_to_id,
)

__all__ = [
    "ActorId",
    "DocId",
    "DocUrl",
    "HyperfileId",
    "HyperfileUrl",
    "RepoId",
    "to_doc_url",
    "to_hyperfile_url",
    "url_to_id",
    "__version__",
]


try:  # re-export the runtime facade once it exists (built in later milestones)
    from .repo import Repo  # noqa: F401

    __all__.append("Repo")
except ImportError:  # pragma: no cover - during early bootstrap only
    pass
