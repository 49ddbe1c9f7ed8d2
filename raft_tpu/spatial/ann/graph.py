"""Fixed-degree kNN-graph ANN index + one-dispatch batched beam search
(ISSUE 19; ROADMAP item 3) — the TPU rework of the reference lineage's
low-latency answer (RAFT grew into CAGRA, Ootomo et al.; itself the GPU
rework of graph methods like HNSW).

IVF is a throughput design: its serving cost is dominated by scanning
``n_probes`` whole lists per query, which amortizes beautifully across a
batch and poorly at batch size 1. A fixed-degree graph index walks
toward the query instead: each hop gathers ``beam x degree`` candidate
rows, scores them, and keeps the best ``beam`` — touching
``iters x beam x degree`` rows total, orders of magnitude fewer than an
IVF probe at the same recall in the qcap<=8 regime.

**Construction** (:func:`graph_build`): start from
:func:`raft_tpu.sparse.knn_graph.knn_graph` (whose ``symmetrize=True``
IS the reverse-edge augment — A ∪ Aᵀ), then a degree-bounded
rank/detour prune (the CAGRA/DiskANN occlusion rule: a candidate ``v``
is dropped when some already-kept closer neighbor ``w`` gives a shorter
detour, ``d(w, v) < d(u, v)``), then pad every row to a static
``(n + 1, degree)`` int32 adjacency with ``-1`` (CAGRA-style; the extra
row is the sentinel node's all-invalid edge list). Construction is a
host-side (numpy) one-off, exactly like the IVF builders' k-means
labeling; only search is a traced program.

**Search** (:func:`graph_search`): batched greedy beam search as ONE
jitted program — no host round-trips, no data-dependent shapes:

* a fixed-width candidate pool of ``P = max(k, beam) + beam`` slots per
  query carries (distance, id, expanded?) triples; every iteration
  expands the ``beam`` best unexpanded entries (static trip count
  ``iters`` — the data-dependent "converged?" loop of CPU HNSW is
  exactly the retrace/host-sync hazard the ``data-dependent-loop-bound``
  lint rule exists for);
* the visited set is a bounded hash table — one byte per slot,
  ``2^hash_bits + 1`` slots per query, marked with a duplicate-safe
  scatter-max — so membership is O(1) with static shape; a collision
  can only DROP a candidate (bounded recall loss, never a wrong
  distance), and the ``+1`` slot is the sentinel's dump bucket;
* distance evaluation routes through the scan-kernel core on the Pallas
  path (:mod:`raft_tpu.spatial.ann.graph_kernel`: bf16 MXU distances,
  8-row sub-chunk minima, top sub-chunks reranked) and through plain
  XLA on the default path — BOTH tails score candidates with
  :func:`raft_tpu.spatial.ann.common.score_l2_candidates`, the grouped
  engines' one exact-rerank authority, so returned distances are exact
  f32 at HIGHEST precision in every configuration;
* the tombstone ``row_mask`` is a runtime operand folded ONLY at the
  exact tail — a dead row still guides navigation (the standard
  graph-index deletion semantics: the walk may pass through it, it can
  never be returned) — so delete/restore flips never retrace. True
  inserts rebuild the graph (the static-adjacency trade the reference
  makes too); the mutation tier's delete/upsert-by-restore cycle is a
  mask flip.

The ``graph_beam`` program-contract entry
(:mod:`raft_tpu.analysis.program.registry`) pins the warmed program's
zero-retrace behavior across health/mutation/route flips, and
``GraphIndex.warmup(audit=True)`` re-audits the exact warmed program
in-process. Serialization rides :mod:`raft_tpu.spatial.ann.serialize`
as its own kind (``graph``, nested ``GraphStorage``) with the CRC
manifest. See docs/graph_ann.md.

Importing this module never imports the kernel modules;
``JAX_PLATFORMS=cpu`` callers reach ``graph_kernel``/``scan_core`` only
through an explicit ``use_pallas`` opt-in (the CPU-subprocess
never-imports test pins this).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import typing
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import compat, errors

__all__ = [
    "GraphParams",
    "GraphStorage",
    "GraphIndex",
    "graph_build",
    "graph_search",
    "graph_live_mask",
    "graph_delete",
    "graph_restore",
]

# Sentinel-row fill value: the padded data row every invalid candidate
# id gathers. Large enough that its squared distance (~d * 1e30) orders
# after every real row in the kernel's sub-chunk minima, finite so no
# inf - inf NaN can form on the VPU (scan_core's BIG discipline), and
# exactly representable in bf16 so the kernel and lax mirrors agree.
_SENTINEL_VAL = 1e15

# Knuth multiplicative hash constant (2^32 / phi) for the visited table.
_HASH_MULT = 2654435761


@dataclasses.dataclass(frozen=True)
class GraphParams:
    """Build knobs of the fixed-degree graph (CAGRA's graph_degree /
    intermediate_graph_degree pair)."""

    degree: int = 16
    # candidate pool per node handed to the occlusion prune (the
    # pre-prune kNN width); None = 2x degree, the CAGRA default ratio
    intermediate_degree: typing.Optional[int] = None
    seed: int = 0
    # deterministic entry points seeding every walk (CAGRA uses random
    # hashes per query; fixed seeded entries keep search reproducible)
    n_entry: int = 4


@compat.register_dataclass
@dataclasses.dataclass
class GraphStorage:
    """The graph half of the index — the nested serialization kind
    (:mod:`raft_tpu.spatial.ann.serialize` registers it like
    ``ListStorage``/``CoarseIndex``)."""

    adjacency: jax.Array   # (n + 1, degree) int32, -1 padded; row n all -1
    entries: jax.Array     # (n_entry,) int32 — seeded walk entry points

    @property
    def n(self) -> int:
        return self.adjacency.shape[0] - 1

    @property
    def degree(self) -> int:
        return self.adjacency.shape[1]


@compat.register_dataclass
@dataclasses.dataclass
class GraphIndex:
    data_padded: jax.Array   # (n + 1, d) — last row is the sentinel
    storage: GraphStorage
    metric: str = dataclasses.field(metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.storage.n

    def warmup(self, nq: int, *, k: int = 10, beam: int = 32,
               iters: typing.Optional[int] = None,
               hash_bits: typing.Optional[int] = None,
               use_pallas: typing.Optional[bool] = None,
               pallas_interpret: bool = False,
               with_mask: bool = False, audit: bool = False) -> int:
        """Pre-compile the beam-search serving program for (nq, d)
        float32 batches: one all-zeros batch is dispatched through the
        exact serving entry and blocked on, so the first real query
        batch pays dispatch, not trace+compile (docs/serving.md) — the
        graph sibling of ``IVFFlatIndex.warmup``.

        ``iters`` resolves shape-only (None -> :func:`_auto_iters`) and
        the resolved value is returned: pass exactly that integer on
        every serving dispatch, the warmed program is keyed on it.
        ``with_mask=True`` warms the tombstone variant instead (a
        ``row_mask`` operand in the signature is a different traced
        program; mask VALUE flips never retrace — the ``graph_beam``
        contract pins this).

        ``audit=True`` additionally traces the warmed program through
        the jaxpr-level program auditor (docs/static_analysis.md "Two
        tiers") and raises listing the findings if it violates the
        serving-tier invariants."""
        n, d = self.n, self.data_padded.shape[1]
        it = _auto_iters(n) if iters is None else iters
        hb = _auto_hash_bits(it, beam, self.storage.degree,
                             self.storage.entries.shape[0]) \
            if hash_bits is None else hash_bits
        q0 = jnp.zeros((nq, d), jnp.float32)
        mask = graph_live_mask(self) if with_mask else None
        out = graph_search(
            self, q0, k, beam=beam, iters=it, hash_bits=hb,
            row_mask=mask, use_pallas=use_pallas,
            pallas_interpret=pallas_interpret,
        )
        jax.block_until_ready(out)
        if audit:
            from raft_tpu.analysis.program import audit_warmed
            from raft_tpu.analysis.program.registry import (
                trace_graph_beam,
            )

            up = _resolve_beam_engine(
                use_pallas, d, beam * self.storage.degree
            )
            audit_warmed(trace_graph_beam(
                self, nq, k, beam, it, hb, with_mask=with_mask,
                use_pallas=up, pallas_interpret=pallas_interpret,
                name="graph_beam_warm",
            ))
        return it


def _auto_iters(n: int) -> int:
    """Default hop budget: the walk covers a small-world graph in
    O(log n) hops; the +4 margin absorbs prune-induced detours. Static
    (a trace-time constant) by construction — the convergence test a
    CPU implementation would loop on is the exact retrace hazard."""
    return min(32, max(4, int(math.ceil(math.log2(max(n, 2)))) + 4))


def _auto_hash_bits(iters: int, beam: int, degree: int,
                    n_entry: int) -> int:
    """Visited-table width: ~8 slots per possible insertion keeps the
    birthday-collision drop rate (a bounded recall effect, never a
    correctness one) low; clamped so the per-query table stays between
    1 KiB and 1 MiB."""
    marks = max(2, n_entry + iters * beam * degree)
    return min(20, max(10, int(math.ceil(math.log2(8 * marks)))))


def _resolve_beam_engine(use_pallas, d: int, c: int) -> bool:
    """Resolve the ``use_pallas`` knob of the beam search to a concrete
    engine choice (a trace-time static) — the graph sibling of
    ``ivf_flat._resolve_scan_engine``. ``c`` is the per-iteration
    candidate count (``beam * degree``).

    ``None`` (auto): the Pallas beam-scan engine on a TPU backend
    whenever the config fits the kernel's VMEM plan; the XLA scorer
    otherwise — so ``JAX_PLATFORMS=cpu`` never imports, let alone
    compiles, the kernel unless a caller opts in explicitly. ``True``
    validates and raises with the reason when unsupported (explicit
    opt-in must not silently fall back)."""
    if use_pallas is None:
        if jax.default_backend() != "tpu":
            return False
        from raft_tpu.spatial.ann import graph_kernel as gk

        c_pad = gk.scan_core.round_up(c, gk.scan_core.LANE)
        return gk.scan_core.auto_kernel(
            gk.beam_scan_supported(d, c_pad), "graph", f"d={d} c={c}"
        )
    if use_pallas:
        from raft_tpu.spatial.ann import graph_kernel as gk

        c_pad = gk.scan_core.round_up(c, gk.scan_core.LANE)
        errors.expects(
            gk.beam_scan_supported(d, c_pad),
            "use_pallas=True unsupported at d=%d candidates=%d (one "
            "query block + candidate tile exceeds the kernel's VMEM "
            "plan); use the XLA scorer (use_pallas=False)", d, c,
        )
    return bool(use_pallas)


# ---------------------------------------------------------------------------
# construction


def graph_build(x, params: GraphParams = GraphParams(), *,
                metric: str = "l2") -> GraphIndex:
    """Build the fixed-degree graph index: kNN graph (reverse-edge
    augmented via ``symmetrize``) -> occlusion prune -> static padded
    adjacency. Deterministic for a given (x, params): the kNN stage,
    the prune, and the seeded entry points all are."""
    from raft_tpu.sparse.knn_graph import knn_graph

    x = jnp.asarray(x)
    errors.check_matrix(x, "x", min_rows=2)
    n, d = x.shape
    deg = min(params.degree, n - 1)
    errors.expects(deg >= 1, "degree must be >= 1, got %d", params.degree)
    idg = params.intermediate_degree
    idg = 2 * deg if idg is None else idg
    idg = min(max(idg, deg), n - 1)

    g = knn_graph(x, idg, symmetrize=True)
    nnz = int(g.nnz)
    rows = np.asarray(g.rows)[:nnz].astype(np.int64)
    cols = np.asarray(g.cols)[:nnz].astype(np.int64)
    xf = np.asarray(x, dtype=np.float32)
    adjacency = _occlusion_prune(xf, rows, cols, deg, 2 * idg)

    rng = np.random.default_rng(params.seed)
    n_entry = max(1, min(params.n_entry, n))
    entries = np.sort(
        rng.choice(n, size=n_entry, replace=False)
    ).astype(np.int32)
    adjacency = _patch_reachability(adjacency, entries, xf)

    adj_pad = np.concatenate(
        [adjacency, np.full((1, deg), -1, np.int32)]
    )
    data_padded = jnp.concatenate(
        [x, jnp.full((1, d), _SENTINEL_VAL, x.dtype)]
    )
    storage = GraphStorage(jnp.asarray(adj_pad), jnp.asarray(entries))
    return GraphIndex(data_padded, storage, metric)


def _occlusion_prune(xf: np.ndarray, rows: np.ndarray, cols: np.ndarray,
                     degree: int, m_cap: int,
                     block: int = 1024) -> np.ndarray:
    """Degree-bounded rank/detour prune of a (row-sorted) COO edge list
    to a dense (n, degree) int32 adjacency, -1 padded.

    Per node ``u``, candidates are visited in ascending d(u, ·) order;
    candidate ``v`` is kept unless an already-kept ``w`` occludes it
    (``d(w, v) < d(u, v)`` — the detour through ``w`` is shorter).
    Slots left after the prune are back-filled with the nearest
    occluded candidates (CAGRA keeps the degree fixed: the diversity
    rule picks WHICH edges, the budget is spent regardless), so rows
    only pad with -1 when the node has fewer candidates than slots.
    Host-side numpy, blocked to bound the (B, m, m) pairwise tile."""
    n, _ = xf.shape
    counts = np.bincount(rows, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)])
    m = int(min(counts.max(initial=1), max(degree, m_cap)))
    cand = np.full((n, m), -1, np.int64)
    within = np.arange(len(rows)) - starts[rows]
    sel = within < m
    cand[rows[sel], within[sel]] = cols[sel]

    out = np.full((n, degree), -1, np.int32)
    arange_m = np.arange(m)
    for b0 in range(0, n, block):
        b1 = min(b0 + block, n)
        B = b1 - b0
        cb = cand[b0:b1]
        valid = (cb >= 0) & (cb != (np.arange(b0, b1)[:, None]))
        cv = np.where(valid, cb, 0)
        # drop duplicate ids (symmetrize combines, but stay safe): keep
        # the first occurrence in id order
        ido = np.argsort(cv + np.where(valid, 0, n + 1), axis=1,
                         kind="stable")
        sid = np.take_along_axis(cv, ido, axis=1)
        dup_sorted = np.zeros((B, m), bool)
        dup_sorted[:, 1:] = sid[:, 1:] == sid[:, :-1]
        dup = np.zeros((B, m), bool)
        np.put_along_axis(dup, ido, dup_sorted, axis=1)
        valid &= ~dup

        diff = xf[b0:b1, None, :] - xf[cv]                 # (B, m, d)
        cd = np.einsum("bmd,bmd->bm", diff, diff)
        cd[~valid] = np.inf
        order = np.argsort(cd, axis=1, kind="stable")      # by distance
        cs = np.take_along_axis(cv, order, axis=1)
        cdist = np.take_along_axis(cd, order, axis=1)
        vs = np.take_along_axis(valid, order, axis=1)

        cvecs = xf[cs]                                     # (B, m, d)
        nn = np.einsum("bmd,bmd->bm", cvecs, cvecs)
        pw = (nn[:, :, None] + nn[:, None, :]
              - 2.0 * np.einsum("bmd,bnd->bmn", cvecs, cvecs))

        kept = np.zeros((B, m), bool)
        occl = ~vs
        kept_count = np.zeros(B, np.int64)
        rng_b = np.arange(B)
        for _ in range(m):
            avail = ~occl & ~kept
            has = avail.any(axis=1) & (kept_count < degree)
            if not has.any():
                break
            first = np.argmax(avail, axis=1)
            kept[rng_b[has], first[has]] = True
            kept_count += has
            newocc = has[:, None] & (pw[rng_b, first] < cdist)
            occl |= newocc
        # kept first, then occluded-but-valid back-fill, both in
        # distance order; invalid last
        klass = np.where(kept, 0, np.where(vs, 1, 2))
        fill = np.argsort(klass, axis=1, kind="stable")[:, :degree]
        ids = np.take_along_axis(cs, fill, axis=1)
        bad = np.take_along_axis(klass, fill, axis=1) == 2
        out[b0:b1] = np.where(bad, -1, ids).astype(np.int32)
    return out


def _patch_reachability(adj: np.ndarray, entries: np.ndarray,
                        xf: np.ndarray) -> np.ndarray:
    """Guarantee every row is reachable from the seeded entries — an
    unreachable row can never be returned at ANY beam width, a permanent
    recall hole. The occlusion prune is per-node (directed): a node can
    lose all its IN-edges even though ``symmetrize`` gave it candidates.
    For each unreached node, overwrite the LAST unclaimed adjacency slot
    (the farthest kept edge — the least diversity lost) of its nearest
    reached node with a reverse edge to it; re-BFS and repeat, since new
    edges cascade. Each slot is claimed at most once, so this
    terminates; deterministic (pure argsort/argmin on distances)."""
    n, degree = adj.shape
    claimed: dict = {}
    for _ in range(n):
        seen = np.zeros(n, bool)
        seen[entries] = True
        frontier = np.asarray(entries, np.int64)
        while frontier.size:
            nxt = adj[frontier].ravel()
            nxt = nxt[nxt >= 0]
            nxt = np.unique(nxt[~seen[nxt]])
            seen[nxt] = True
            frontier = nxt
        miss = np.flatnonzero(~seen)
        if not miss.size:
            break
        reach = np.flatnonzero(seen)
        progressed = False
        for u in miss:
            d2 = ((xf[reach] - xf[u]) ** 2).sum(axis=1)
            for w in reach[np.argsort(d2, kind="stable")]:
                slot = degree - 1 - claimed.get(int(w), 0)
                if slot < 0:
                    continue
                adj[w, slot] = u
                claimed[int(w)] = claimed.get(int(w), 0) + 1
                progressed = True
                break
        if not progressed:      # every reached row fully claimed —
            break               # degenerate; leave the remainder
    return adj


# ---------------------------------------------------------------------------
# mutation (tombstone) helpers — the mask is a RUNTIME operand of the
# beam program; flipping values never retraces (the graph_beam contract
# pins it). True inserts rebuild the graph.


def graph_live_mask(index: GraphIndex) -> jax.Array:
    """All-live (n,) int8 tombstone mask for ``index``."""
    return jnp.ones((index.n,), jnp.int8)


def graph_delete(row_mask: jax.Array, ids) -> jax.Array:
    """Tombstone rows: deleted rows still guide the walk, never appear
    in results (folded at the exact rerank tail only)."""
    return row_mask.at[jnp.asarray(ids)].set(0)


def graph_restore(row_mask: jax.Array, ids) -> jax.Array:
    """Un-tombstone rows (the upsert-by-restore half of the mutation
    cycle)."""
    return row_mask.at[jnp.asarray(ids)].set(1)


# ---------------------------------------------------------------------------
# search


def graph_search(index: GraphIndex, queries, k: int, *, beam: int = 32,
                 iters: typing.Optional[int] = None,
                 hash_bits: typing.Optional[int] = None,
                 row_mask: typing.Optional[jax.Array] = None,
                 use_pallas: typing.Optional[bool] = None,
                 pallas_interpret: bool = False,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Batched greedy beam search — ONE jitted dispatch per call.

    Returns (dists, ids) with original row ids, -1 where fewer than
    ``k`` reachable live rows exist; L2 metric family (squared
    distances, sqrt applied for metric='l2'), exact f32 at HIGHEST
    precision via the shared rerank tail."""
    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.data_padded, "queries", "index")
    n = index.n
    errors.check_k(k, n, "k vs graph rows")
    errors.expects(beam >= 1, "beam must be >= 1, got %d", beam)
    it = _auto_iters(n) if iters is None else iters
    hb = _auto_hash_bits(it, beam, index.storage.degree,
                         index.storage.entries.shape[0]) \
        if hash_bits is None else hash_bits
    up = _resolve_beam_engine(
        use_pallas, index.data_padded.shape[1],
        beam * index.storage.degree,
    )
    vals, ids = _beam_impl(
        index, q, k=k, beam=beam, iters=it, hash_bits=hb,
        row_mask=row_mask, use_pallas=up,
        pallas_interpret=pallas_interpret,
    )
    if index.metric == "l2":
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, ids


@functools.partial(
    jax.jit,
    static_argnames=("k", "beam", "iters", "hash_bits", "use_pallas",
                     "pallas_interpret"),
)
def _beam_impl(index, q, k, beam, iters, hash_bits, row_mask=None,
               use_pallas=False, pallas_interpret=False):
    # The whole walk — init, ``iters`` expansion rounds, exact tail —
    # is one traced program. Static geometry: pool width
    # P = max(k, beam) + beam (>= beam unexpanded slots survive a full
    # expansion round, >= k for the tail), candidate buffer
    # C = beam * degree, visited table 2^hash_bits + 1 bytes/query.
    # ``row_mask`` (n,) int8 is a RUNTIME operand folded only at the
    # tail; ``None`` omits the operand (a separate warmed program).
    n = index.storage.adjacency.shape[0] - 1
    degree = index.storage.adjacency.shape[1]
    nq = q.shape[0]
    qf = q.astype(jnp.float32)
    P = max(k, beam) + beam
    C = beam * degree
    T = 1 << hash_bits
    rows = jnp.arange(nq, dtype=jnp.int32)[:, None]

    from raft_tpu.spatial.ann.common import score_l2_candidates

    def _hash(ids):
        # Knuth multiplicative hash, uint32 throughout (the program
        # contracts forbid 64-bit dtype flow); sentinel -> dump slot T
        u = ids.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)
        h = (u >> np.uint32(32 - hash_bits)).astype(jnp.int32)
        return jnp.where(ids < n, h, T)

    def _score_exact(cand):
        # the one rerank authority: exact f32, +inf where invalid
        cvec = index.data_padded[cand].astype(jnp.float32)
        return score_l2_candidates(qf, cvec, cand < n)

    if use_pallas:
        from raft_tpu.spatial.ann import graph_kernel as gk

        c_pad = gk.scan_core.round_up(C, gk.scan_core.LANE)
        l_tile = gk.plan_l_tile(index.data_padded.shape[1],
                                gk.pad_queries(1))
        while c_pad % l_tile:
            l_tile -= gk.scan_core.LANE
        q_pad = gk.pad_queries(1)
        qrows = jnp.zeros((nq, q_pad, qf.shape[1]), jnp.float32)
        qrows = qrows.at[:, 0, :].set(qf)
        bounds = jnp.broadcast_to(
            jnp.array([0, c_pad], jnp.int32), (nq, 2)
        )
        # cover argument: the top-s sub-chunks by minimum contain the
        # top-s candidate rows, so s = P sub-chunks cover every row the
        # pool merge could keep
        s = min(c_pad // gk.SUBCHUNK, P)

        def _score_new(cand):
            cp = jnp.concatenate(
                [cand, jnp.full((nq, c_pad - C), n, jnp.int32)], axis=1
            )
            cvec = index.data_padded[cp]                  # (nq, Cp, d)
            mins = gk.beam_scan_subchunk_min(
                qrows, cvec.transpose(0, 2, 1), bounds,
                interpret=pallas_interpret, l_tile=l_tile,
            )[:, 0]                                       # (nq, Cp/8)
            _, sub = lax.top_k(-mins, s)
            pos = (
                sub[:, :, None] * gk.SUBCHUNK
                + jnp.arange(gk.SUBCHUNK, dtype=jnp.int32)
            ).reshape(nq, s * gk.SUBCHUNK)
            csel = jnp.take_along_axis(cp, pos, axis=1)
            csub = jnp.take_along_axis(
                cvec, pos[:, :, None], axis=1
            ).astype(jnp.float32)
            return score_l2_candidates(qf, csub, csel < n), csel
    else:

        def _score_new(cand):
            return _score_exact(cand), cand

    # init: seeded entries fill the first pool slots (scored exactly),
    # the rest hold the sentinel at +inf
    e = index.storage.entries[: min(index.storage.entries.shape[0], P)]
    E = e.shape[0]
    ed = _score_exact(jnp.broadcast_to(e[None, :], (nq, E)))
    pool_d = jnp.full((nq, P), jnp.inf, jnp.float32).at[:, :E].set(ed)
    pool_i = jnp.full((nq, P), n, jnp.int32).at[:, :E].set(
        jnp.broadcast_to(e, (nq, E))
    )
    pool_x = jnp.zeros((nq, P), bool)
    visited = jnp.zeros((nq, T + 1), jnp.uint8).at[:, _hash(e)].max(
        jnp.uint8(1)
    )

    def body(_, state):
        pool_d, pool_i, pool_x, visited = state
        # frontier: best `beam` unexpanded live entries
        sel_key = jnp.where(pool_x | (pool_i >= n), jnp.inf, pool_d)
        neg, sel = lax.top_k(-sel_key, beam)
        fvalid = jnp.isfinite(neg)
        pool_x = pool_x.at[rows, sel].set(True)
        fids = jnp.take_along_axis(pool_i, sel, axis=1)
        fids = jnp.where(fvalid, fids, n)
        # gather neighbors (sentinel frontier row is all -1)
        cand = index.storage.adjacency[fids].reshape(nq, C)
        cand = jnp.where(cand < 0, n, cand)
        # within-round dedup: sort, tombstone equal neighbors
        cand = jnp.sort(cand, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((nq, 1), bool), cand[:, 1:] == cand[:, :-1]],
            axis=1,
        )
        cand = jnp.where(dup, n, cand)
        # visited filter + duplicate-safe mark
        seen = jnp.take_along_axis(visited, _hash(cand), axis=1) > 0
        cand = jnp.where(seen, n, cand)
        visited = visited.at[rows, _hash(cand)].max(jnp.uint8(1))
        # score + merge: keep the best P of pool ∪ new
        new_d, new_i = _score_new(cand)
        all_d = jnp.concatenate([pool_d, new_d], axis=1)
        all_i = jnp.concatenate([pool_i, new_i], axis=1)
        all_x = jnp.concatenate(
            [pool_x, jnp.zeros(new_i.shape, bool)], axis=1
        )
        top, idx = lax.top_k(-all_d, P)
        return (
            -top,
            jnp.take_along_axis(all_i, idx, axis=1),
            jnp.take_along_axis(all_x, idx, axis=1),
            visited,
        )

    pool_d, pool_i, pool_x, visited = lax.fori_loop(
        0, iters, body, (pool_d, pool_i, pool_x, visited)
    )

    # exact tail — the ONLY place tombstones fold, so mask flips are
    # pure runtime and the walk still navigates through dead rows
    live = pool_i < n
    if row_mask is not None:
        live &= row_mask[jnp.clip(pool_i, 0, n - 1)] > 0
    cvec = index.data_padded[pool_i].astype(jnp.float32)
    d2 = score_l2_candidates(qf, cvec, live)
    neg, pos = lax.top_k(-d2, k)
    vals = -neg
    ids = jnp.take_along_axis(pool_i, pos, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids.astype(jnp.int32)
