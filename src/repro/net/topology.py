"""Per-worker directed *hearing graphs* (DESIGN.md §15).

The paper's single-hop radio assumption is that every worker overhears
every other worker's slot. A :class:`HearingGraph` makes that assumption
a swappable axis: ``adj[j][i]`` says whether worker j's radio hears
worker i's broadcast. Under a partial graph the protocol slot loop keeps
one view of the reference set per worker — worker j may only echo against
raws it actually overheard, and the server (which hears every uplink slot
regardless) provably detects echoes referencing gradients outside the
sender's hearing set.

Graphs are frozen, hashable (tuple-of-tuples adjacency) so they ride as
jit static args next to ``ProtocolConfig``; :meth:`HearingGraph.matrix`
materialises the (n, n) bool array at trace time.

``TOPOLOGIES`` is the shared plugin registry (``repro.run.registry``):
a builder takes ``(spec, n)`` where ``spec`` is the job's
``scenario.net`` section (:class:`repro.run.config.NetSpec`) and n the
worker count.

    complete            the paper's all-hear set (the bitwise-identical
                        default — the slot loop keeps one view, shared
                        by every worker)
    ring                workers on a cycle hear ``degree // 2``
                        neighbours on each side
    random_geometric    seeded uniform placement on the unit square;
                        j hears i iff their distance is under the radius
                        that targets an average degree of ``degree``
    explicit            adjacency rows from the spec string, e.g.
                        "011;101;110" (row j, column i, no self-loops)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

from repro.run.registry import TOPOLOGIES


@dataclasses.dataclass(frozen=True)
class HearingGraph:
    """Directed overhearing relation: ``adj[j][i]`` = j hears i's slot.

    ``strict=True`` makes even a complete adjacency count as partial:
    the slot loop then keeps n views of the reference set, one per
    worker, instead of the one they share (tests use it to compare n
    coinciding views against the one).
    """

    adj: Tuple[Tuple[bool, ...], ...]
    strict: bool = False

    def __post_init__(self):
        n = len(self.adj)
        if any(len(row) != n for row in self.adj):
            raise ValueError(f"hearing graph adjacency must be square, "
                             f"got rows of lengths "
                             f"{[len(r) for r in self.adj]}")
        if any(self.adj[j][j] for j in range(n)):
            raise ValueError("hearing graph must not contain self-loops "
                             "(a worker never re-hears its own slot)")

    @property
    def n(self) -> int:
        return len(self.adj)

    @property
    def is_complete(self) -> bool:
        """All-hear set: every off-diagonal edge present (the paper's
        assumption — the slot loop keeps one shared view); never under
        ``strict``."""
        if self.strict:
            return False
        n = self.n
        return all(self.adj[j][i] for j in range(n) for i in range(n)
                   if i != j)

    def edge_count(self) -> int:
        return sum(sum(row) for row in self.adj)

    def matrix(self):
        """(n, n) bool jnp array — trace-time materialisation."""
        import jax.numpy as jnp
        return jnp.asarray(self.adj, dtype=bool)


def complete_graph(n: int) -> HearingGraph:
    adj = tuple(tuple(i != j for i in range(n)) for j in range(n))
    return HearingGraph(adj=adj)


def ring_graph(n: int, degree: int = 2) -> HearingGraph:
    """Cycle topology: j hears the ``degree // 2`` nearest workers on
    each side (degree=2 is the classic bidirectional ring)."""
    if degree < 2 or degree % 2:
        raise ValueError(f"ring degree must be a positive even number "
                         f"(neighbours split across both sides), "
                         f"got {degree}")
    half = min(degree // 2, n - 1)

    def hears(j: int, i: int) -> bool:
        if i == j:
            return False
        dist = min((j - i) % n, (i - j) % n)
        return dist <= half

    adj = tuple(tuple(hears(j, i) for i in range(n)) for j in range(n))
    return HearingGraph(adj=adj)


def random_geometric_graph(n: int, degree: int = 2,
                           seed: int = 0) -> HearingGraph:
    """Seeded uniform placement on the unit square; j hears i iff
    ``dist(j, i) <= radius`` with the radius picked so the *expected*
    degree is roughly ``degree`` (area pi r^2 ~ degree / n)."""
    import numpy as np
    if n < 2:
        raise ValueError(f"random_geometric needs n >= 2, got {n}")
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.0, 1.0, size=(n, 2))
    radius = math.sqrt(max(degree, 1) / (n * math.pi))
    d2 = ((pos[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
    hears = d2 <= radius * radius
    np.fill_diagonal(hears, False)
    adj = tuple(tuple(bool(v) for v in row) for row in hears)
    return HearingGraph(adj=adj)


def explicit_graph(adjacency: str, n: int) -> HearingGraph:
    """Parse "011;101;110"-style rows (row j, column i; '1' = j hears
    i). The matrix must be n x n and self-loop free."""
    rows = [r.strip() for r in adjacency.split(";") if r.strip()]
    if len(rows) != n or any(len(r) != n for r in rows):
        raise ValueError(
            f"scenario.net.adjacency must give {n} rows of {n} binary "
            f"digits separated by ';', got {adjacency!r}")
    if any(c not in "01" for r in rows for c in r):
        raise ValueError(f"scenario.net.adjacency rows must be binary "
                         f"strings, got {adjacency!r}")
    adj = tuple(tuple(c == "1" for c in row) for row in rows)
    return HearingGraph(adj=adj)


@TOPOLOGIES.register("complete")
def _build_complete(spec, n: int) -> HearingGraph:
    return complete_graph(n)


@TOPOLOGIES.register("ring")
def _build_ring(spec, n: int) -> HearingGraph:
    return ring_graph(n, degree=getattr(spec, "degree", 2))


@TOPOLOGIES.register("random_geometric")
def _build_random_geometric(spec, n: int) -> HearingGraph:
    return random_geometric_graph(n, degree=getattr(spec, "degree", 2),
                                  seed=getattr(spec, "seed", 0))


@TOPOLOGIES.register("explicit")
def _build_explicit(spec, n: int) -> HearingGraph:
    adjacency = getattr(spec, "adjacency", "")
    if not adjacency:
        raise ValueError("topology 'explicit' needs scenario.net.adjacency "
                         "(e.g. \"011;101;110\")")
    return explicit_graph(adjacency, n)
