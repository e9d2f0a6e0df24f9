"""Plain reference of DisReduA, reduce-and-peel and reconstruction, in numpy.

It imports nothing of the system under test and takes nothing it made: it
builds its own per-PE local graphs from the input CSR and replays the
published rule semantics one array pass at a time.  What it computes is the
answer the system owes for the same input and settings, so the benchmark
compares the system's output with it exactly.

Semantics (arXiv:2510.13306 §4-§6, in the batched form the system states):

* 1D vertex partition, edge-balanced contiguous blocks; each PE holds its
  vertices' edges plus the reversed cut edges, ghost copies of remote
  neighbours, and per-vertex windows of the first ``D`` neighbours in
  (local before ghost, then id) order.  ``Dc`` caps each edge's common
  neighbourhood.
* One sweep snapshots the neighbourhood aggregates (sum S, active degree,
  max neighbour weight M, the unique neighbour, window clique bit) once and
  applies, in order: degree zero/one (include, fold), neighbourhood removal,
  simplicial weight transfer, simplicial vertex, basic single-edge,
  extended single-edge.  Tests read the snapshot, applications read the
  current state.  Concurrent includes keep the candidates that beat every
  candidate neighbour by global id; exclusions need a higher-id
  certificate; a weight transfer must be the highest-id candidate within two
  hops.  A sweep that changes nothing runs the heavy-vertex rule (exact
  weight of the first ``heavy_k`` active window entries).
* A round runs up to ``sweeps`` sweeps per PE, then exchanges interface
  weights and statuses (owner is authoritative; include conflicts across a
  cut edge keep the lower rank's vertex).  Rounds repeat while anything
  changed anywhere.
* Reduce-and-peel: reduce to the fixpoint, exclude each PE's first vertex of
  largest ``w(N(v)) - w(v)``, repeat until no vertex is undecided; replay the
  fold log newest-first to get the members.

``lowp=True`` keeps every neighbourhood weight sum and the folded-weight
offset in bfloat16: the control that breaks the exact-integer guarantee.
"""

from __future__ import annotations

import numpy as np

UNDECIDED, INCLUDED, EXCLUDED, FOLDED = 0, 1, 2, 3
LOG_FOLD1, LOG_WT = 1, 2
I32_MIN = int(np.iinfo(np.int32).min)
I32_MAX = int(np.iinfo(np.int32).max)


def bf16(x: np.ndarray) -> np.ndarray:
    """Round integers through bfloat16 (nearest, ties to even)."""
    f = np.asarray(x, np.float32).view(np.uint32)
    f = (f + np.uint32(0x7FFF) + ((f >> 16) & 1)) & np.uint32(0xFFFF0000)
    return f.view(np.float32).astype(np.int64)


def block_starts(indptr: np.ndarray, p: int) -> np.ndarray:
    """Contiguous blocks with equal shares of the directed edges."""
    n = indptr.shape[0] - 1
    starts = np.searchsorted(indptr, np.linspace(0, indptr[-1], p + 1),
                             side="left")
    starts[0], starts[-1] = 0, n
    return np.maximum.accumulate(starts).astype(np.int64)


class LocalGraph:
    """One PE's local graph: locals, then ghosts, then one nil slot."""

    def __init__(self, indptr, indices, starts, pe, D, Dc):
        lo, hi = int(starts[pe]), int(starts[pe + 1])
        owner_of = lambda ids: np.searchsorted(starts, ids, side="right") - 1
        nloc = hi - lo
        e0, e1 = int(indptr[lo]), int(indptr[hi])
        src = np.repeat(np.arange(lo, hi, dtype=np.int64),
                        np.diff(indptr[lo:hi + 1]))
        dst = indices[e0:e1].astype(np.int64)
        remote = (dst < lo) | (dst >= hi)
        ghosts = np.unique(dst[remote])
        ng = ghosts.shape[0]
        V = nloc + ng + 1
        nil = V - 1
        ldst = np.where(remote, nloc + np.searchsorted(ghosts, dst), dst - lo)
        lsrc = src - lo
        cut = ldst >= nloc
        row = np.concatenate([lsrc, ldst[cut]])
        col = np.concatenate([ldst, lsrc[cut]])
        order = np.lexsort((col, row))
        row, col = row[order], col[order]
        E = row.shape[0]

        gid = np.full(V, -1, np.int64)
        gid[:nloc] = np.arange(lo, hi)
        gid[nloc:nil] = ghosts
        is_local = np.zeros(V, bool)
        is_local[:nloc] = True
        is_ghost = np.zeros(V, bool)
        is_ghost[nloc:nil] = True
        is_iface = np.zeros(V, bool)
        is_iface[lsrc[cut]] = True
        owner = np.full(V, -1, np.int64)
        owner[:nloc] = pe
        owner[nloc:nil] = owner_of(ghosts)

        deg = np.bincount(row, minlength=V)
        first = np.concatenate([[0], np.cumsum(deg)])[:-1]
        pos = np.arange(E) - first[row]
        window = np.full((V, D), nil, np.int64)
        small = pos < D
        window[row[small], pos[small]] = col[small]
        keys = row * V + col      # sorted, since (row, col) is

        # window pair adjacency: bit b of win_adj[v, a] is the edge
        # (window[v, a], window[v, b]); sorted queries keep it cache-friendly
        win_adj = np.zeros((V, D), np.int64)
        real = window != nil
        qv, qa, qb = [], [], []
        for a in range(D):
            for b in range(D):
                rows = np.flatnonzero(real[:, a] & real[:, b])
                if a != b and rows.size:
                    qv.append(rows)
                    qa.append(np.full(rows.size, a))
                    qb.append(np.full(rows.size, b))
        if qv and E:
            qv, qa, qb = (np.concatenate(x) for x in (qv, qa, qb))
            q = window[qv, qa] * V + window[qv, qb]
            order = np.argsort(q)
            at = np.minimum(np.searchsorted(keys, q[order]), E - 1)
            hit = np.empty(q.shape[0], bool)
            hit[order] = keys[at] == q[order]
            np.bitwise_or.at(win_adj, (qv[hit], qa[hit]), 1 << qb[hit])
        common = np.full((E, Dc), nil, np.int64)
        for c0 in range(0, E, 1 << 16):
            wu = window[row[c0:c0 + (1 << 16)]]
            wv = window[col[c0:c0 + (1 << 16)]]
            hit = (wu[:, :, None] == wv[:, None, :]).any(-1) & (wu != nil)
            rank = np.cumsum(hit, axis=1) - 1
            sel = hit & (rank < Dc)
            e_idx, _ = np.nonzero(sel)
            common[c0 + e_idx, rank[sel]] = wu[sel]

        self.pe, self.lo, self.hi, self.nloc, self.ng = pe, lo, hi, nloc, ng
        self.V, self.nil, self.D, self.Dc = V, nil, D, Dc
        self.row, self.col, self.gid = row, col, gid
        self.is_local, self.is_ghost, self.is_iface = is_local, is_ghost, is_iface
        self.owner, self.window, self.win_complete = owner, window, deg <= D
        self.win_adj, self.common = win_adj, common
        self.w0 = np.zeros(V, np.int64)


class State:
    """One PE's reduction state (the system's RedState, on the host)."""

    def __init__(self, lg: LocalGraph):
        self.w = lg.w0.copy()
        self.status = np.where(lg.is_local | lg.is_ghost, UNDECIDED,
                               EXCLUDED).astype(np.int8)
        self.offset = 0
        self.log_kind, self.log_v, self.log_u = [], [], []
        self.changed = False

    def log(self, mask, kind, u):
        v = np.flatnonzero(mask)
        self.log_kind.append(np.full(v.shape[0], kind, np.int64))
        self.log_v.append(v)
        self.log_u.append(u[v])

    def fold_log(self):
        if not self.log_kind:
            z = np.zeros(0, np.int64)
            return z, z, z
        return (np.concatenate(self.log_kind), np.concatenate(self.log_v),
                np.concatenate(self.log_u))


def _seg_sum(vals, idx, V):
    return np.bincount(idx, weights=vals, minlength=V).astype(np.int64)


def _seg_max(vals, idx, V):
    out = np.full(V, I32_MIN, np.int64)
    np.maximum.at(out, idx, vals)
    return out


class Reducer:
    """DisReduA / reduce-and-peel over ``p`` local graphs of one input."""

    def __init__(self, indptr, indices, weights, p, *, D=16, Dc=4,
                 heavy_k=8, use_heavy=True, sweeps=2, max_rounds=10_000,
                 lowp=False):
        indptr = np.asarray(indptr, np.int64)
        indices = np.asarray(indices, np.int64)
        weights = np.asarray(weights, np.int64)
        self.n, self.p = weights.shape[0], p
        self.starts = block_starts(indptr, p)
        self.pes = [LocalGraph(indptr, indices, self.starts, i, D, Dc)
                    for i in range(p)]
        for lg in self.pes:
            lg.w0[:lg.nil] = weights[lg.gid[:lg.nil]]
        self.states = [State(lg) for lg in self.pes]
        self.heavy_k, self.use_heavy = heavy_k, use_heavy
        self.sweeps, self.max_rounds, self.lowp = sweeps, max_rounds, lowp

    # ---------------------------------------------------------------- #
    # one sweep
    # ---------------------------------------------------------------- #
    def _nbr_sum(self, vals, lg):
        s = _seg_sum(vals, lg.row, lg.V)
        return bf16(s) if self.lowp else s

    def _add_offset(self, st, amount):
        st.offset += amount
        if self.lowp:
            st.offset = int(bf16(np.array([st.offset]))[0])

    def _accept(self, lg, eact, cand):
        m = _seg_max(np.where(eact & cand[lg.col], lg.gid[lg.col], -1),
                     lg.row, lg.V)
        return cand & (lg.gid > np.maximum(m, -1))

    def _include(self, lg, st, eact, acc):
        st.status[acc] = INCLUDED
        hit = np.bincount(lg.col[acc[lg.row] & eact], minlength=lg.V) > 0
        st.status[hit & (st.status == UNDECIDED)] = EXCLUDED
        st.changed |= bool(acc.any())

    def _fresh(self, lg, st):
        active = st.status == UNDECIDED
        return active, active[lg.row] & active[lg.col]

    def _window_bits(self, lg, active):
        ok = active[lg.window] & (lg.gid[lg.window] >= 0)
        return (ok.astype(np.int64) << np.arange(lg.D)).sum(axis=1)

    def _snapshot(self, lg, st):
        active, eact = self._fresh(lg, st)
        aw = np.where(active, st.w, 0)
        S = self._nbr_sum(np.where(eact, aw[lg.col], 0), lg)
        deg = np.bincount(lg.row[eact], minlength=lg.V)
        M = _seg_max(np.where(eact, st.w[lg.col], I32_MIN), lg.row, lg.V)
        only = np.maximum(_seg_max(np.where(eact, lg.col, -1), lg.row, lg.V), 0)
        bits = self._window_bits(lg, active)
        sh = np.arange(lg.D)
        on = ((bits[:, None] >> sh) & 1) == 1
        need = bits[:, None] & ~(1 << sh)
        clique = ~(on & ((need & ~lg.win_adj) != 0)).any(axis=1)
        return S, deg, M, only, clique

    def _degree_one(self, lg, st, deg, only):
        V, nil = lg.V, lg.nil
        active, eact = self._fresh(lg, st)
        w_u = st.w[only]
        self._include(lg, st, eact, lg.is_local & active & (deg == 0))
        active, eact = self._fresh(lg, st)
        cand = lg.is_local & active & (deg == 1) & (st.w >= w_u)
        self._include(lg, st, eact, self._accept(lg, eact, cand))
        active = st.status == UNDECIDED
        cand = (lg.is_local & active & (deg == 1) & (st.w < w_u)
                & lg.is_local[only] & active[only])
        best = np.full(V, -1, np.int64)
        np.maximum.at(best, np.where(cand, only, nil),
                      np.where(cand, lg.gid, -1))
        acc = cand & (lg.gid == best[only])
        w_old = st.w.copy()
        np.add.at(st.w, np.where(acc, only, nil), np.where(acc, -w_old, 0))
        st.w[nil] = 0
        st.status[acc] = FOLDED
        self._add_offset(st, int(w_old[acc].sum()))
        st.changed |= bool(acc.any())
        st.log(acc, LOG_FOLD1, only)

    def _neighborhood_removal(self, lg, st, S):
        active, eact = self._fresh(lg, st)
        cand = lg.is_local & active & (st.w >= S)
        self._include(lg, st, eact, self._accept(lg, eact, cand))

    def _weight_transfer(self, lg, st, clique, M, deg):
        V, nil = lg.V, lg.nil
        active, eact = self._fresh(lg, st)
        simpl = lg.win_complete & clique
        blocks = eact & (st.w[lg.col] > st.w[lg.row]) & (
            simpl[lg.col] | ~lg.win_complete[lg.col])
        blocked = np.bincount(lg.row[blocks], minlength=V) > 0
        cand = (lg.is_local & active & ~lg.is_iface & simpl & (st.w < M)
                & ~blocked & (deg >= 1))
        m1 = np.maximum(_seg_max(np.where(eact & cand[lg.col],
                                          lg.gid[lg.col], -1), lg.row, V), -1)
        m2 = np.maximum(_seg_max(np.where(eact, m1[lg.col], -1), lg.row, V),
                        -1)
        acc = cand & (lg.gid > m1) & (lg.gid >= m2)
        bits = self._window_bits(lg, active)
        ent = ((bits[:, None] >> np.arange(lg.D)) & 1) == 1
        wv = st.w.copy()
        tgt = lg.window
        excl = acc[:, None] & ent & (wv[tgt] <= wv[:, None])
        dec = acc[:, None] & ent & (wv[tgt] > wv[:, None])
        st.status[np.where(excl, tgt, nil)] = EXCLUDED
        st.status[acc] = FOLDED
        np.add.at(st.w, np.where(dec, tgt, nil),
                  np.where(dec, -wv[:, None], 0))
        st.w[nil] = 0
        self._add_offset(st, int(wv[acc].sum()))
        st.changed |= bool(acc.any())
        st.log(acc, LOG_WT, np.arange(V))

    def _simplicial(self, lg, st, clique, M):
        active, eact = self._fresh(lg, st)
        cand = lg.is_local & active & lg.win_complete & clique & (st.w >= M)
        self._include(lg, st, eact, self._accept(lg, eact, cand))

    def _common_weight(self, lg, active, aw):
        c = lg.common
        return np.where(active[c], aw[c], 0).sum(axis=1)

    def _basic_single_edge(self, lg, st, S):
        active, eact = self._fresh(lg, st)
        aw = np.where(active, st.w, 0)
        cw = self._common_weight(lg, active, aw)
        r, c = lg.row, lg.col
        test = (eact & lg.is_local[r] & lg.is_local[c]
                & (S[r] - cw <= st.w[r]) & (lg.gid[r] > lg.gid[c]))
        excl = np.bincount(c[test], minlength=lg.V) > 0
        fired = excl & active & lg.is_local
        st.status[fired] = EXCLUDED
        st.changed |= bool(fired.any())

    def _extended_single_edge(self, lg, st, S):
        active, eact = self._fresh(lg, st)
        aw = np.where(active, st.w, 0)
        r, c = lg.row, lg.col
        test = (eact & lg.is_local[r] & lg.is_local[c]
                & (S[r] - aw[c] <= st.w[r]))
        min_gid = np.minimum(lg.gid[r], lg.gid[c])
        tgt = lg.common
        upd = (test[:, None] & active[tgt] & lg.is_local[tgt]
               & (lg.gid[tgt] < min_gid[:, None]) & (lg.gid[tgt] >= 0))
        st.status[np.where(upd, tgt, lg.nil)] = EXCLUDED
        st.changed |= bool(upd.any())

    def _alpha(self, lg, st, rows):
        """Exact weight of the first heavy_k active window entries."""
        K = self.heavy_k
        active = st.status == UNDECIDED
        win = lg.window[rows]
        ok = active[win] & (lg.gid[win] >= 0)
        order = np.argsort(~ok, axis=1, kind="stable")[:, :K]
        ent = np.take_along_axis(win, order, axis=1)
        act = np.take_along_axis(ok, order, axis=1)
        wk = np.where(act, st.w[ent], 0)
        bits = np.take_along_axis(lg.win_adj[rows], order, axis=1)
        adj = np.zeros_like(wk)
        for j in range(K):
            adj |= ((bits >> order[:, j:j + 1]) & 1) << j
        subsets = np.arange(1 << K)
        sel = (subsets[:, None] >> np.arange(K)) & 1
        totals = wk @ sel.T
        conflict = np.zeros(totals.shape, bool)
        for i in range(K):
            conflict |= (sel[:, i] == 1)[None, :] & (
                (subsets[None, :] & adj[:, i:i + 1]) != 0)
        return np.maximum(np.where(conflict, -1, totals).max(axis=1), 0)

    def _heavy_vertex(self, lg, st):
        active, eact = self._fresh(lg, st)
        deg = np.bincount(lg.row[eact], minlength=lg.V)
        pre = lg.is_local & active & lg.win_complete & (deg <= self.heavy_k)
        rows = np.flatnonzero(pre)
        alpha = np.zeros(lg.V, np.int64)
        for c0 in range(0, rows.shape[0], 1 << 14):
            blk = rows[c0:c0 + (1 << 14)]
            alpha[blk] = self._alpha(lg, st, blk)
        cand = pre & (st.w >= alpha)
        self._include(lg, st, eact, self._accept(lg, eact, cand))

    def _sweep(self, lg, st):
        S, deg, M, only, clique = self._snapshot(lg, st)
        self._degree_one(lg, st, deg, only)
        self._neighborhood_removal(lg, st, S)
        self._weight_transfer(lg, st, clique, M, deg)
        self._simplicial(lg, st, clique, M)
        self._basic_single_edge(lg, st, S)
        self._extended_single_edge(lg, st, S)

    def _local_reduce(self, lg, st):
        st.changed, it = True, 0
        while st.changed and it < self.sweeps:
            st.changed = False
            self._sweep(lg, st)
            if self.use_heavy and not st.changed:
                self._heavy_vertex(lg, st)
            it += 1

    # ---------------------------------------------------------------- #
    # exchange, rounds, peeling
    # ---------------------------------------------------------------- #
    def _exchange(self):
        if self.p == 1:
            return
        board = [(st.w.copy(), st.status.copy()) for st in self.states]
        for lg, st in zip(self.pes, self.states):
            V = lg.V
            gh = np.flatnonzero(lg.is_ghost)
            own = lg.owner[gh]
            src = lg.gid[gh] - self.starts[own]
            bw = np.full(V, I32_MAX, np.int64)
            bs = np.full(V, -1, np.int64)
            for o in np.unique(own):
                sel = own == o
                bw[gh[sel]] = board[o][0][src[sel]]
                bs[gh[sel]] = board[o][1][src[sel]]
            r, c = lg.row, lg.col
            ginc = bs == INCLUDED
            prop = (st.status == INCLUDED) & lg.is_iface
            rank_r, rank_c = lg.owner[r], lg.owner[c]
            v_lose_e = prop[r] & ginc[c] & (lg.gid[c] >= 0) & (rank_c < rank_r)
            u_lose_e = ginc[r] & prop[c] & (lg.gid[r] >= 0) & (rank_c < rank_r)
            v_lose = np.bincount(r[v_lose_e], minlength=V) > 0
            u_lose = np.bincount(r[u_lose_e], minlength=V) > 0
            status = st.status.astype(np.int64)
            status[v_lose & (status == INCLUDED)] = EXCLUDED
            slot = bs >= 0
            new = np.where((bs == INCLUDED) & ~u_lose, INCLUDED, np.where(
                (bs == EXCLUDED) | (bs == FOLDED) | ((bs == INCLUDED) & u_lose),
                EXCLUDED, status))
            status = np.where(slot, new, status)
            st.w = np.where(slot, np.minimum(st.w, bw), st.w)
            ginc_now = slot & (status == INCLUDED)
            hit = np.bincount(r[ginc_now[c]], minlength=V) > 0
            status[hit & (status == UNDECIDED) & lg.is_local] = EXCLUDED
            st.status = status.astype(np.int8)

    def _rounds_to_fixpoint(self) -> int:
        rounds, changed = 0, True
        while changed and rounds < self.max_rounds:
            snap = [(st.status.copy(), st.w.copy()) for st in self.states]
            for lg, st in zip(self.pes, self.states):
                self._local_reduce(lg, st)
            self._exchange()
            changed = any((st.status != s).any() or (st.w != w).any()
                          for st, (s, w) in zip(self.states, snap))
            rounds += 1
        return rounds

    def _remaining(self) -> bool:
        return any((lg.is_local & (st.status == UNDECIDED)).any()
                   for lg, st in zip(self.pes, self.states))

    def _peel(self):
        for lg, st in zip(self.pes, self.states):
            active, eact = self._fresh(lg, st)
            aw = np.where(active, st.w, 0)
            s = self._nbr_sum(np.where(eact, aw[lg.col], 0), lg)
            score = np.where(lg.is_local & active, s - st.w, I32_MIN)
            top = int(np.argmax(score))
            if score[top] > I32_MIN:
                st.status[top] = EXCLUDED

    def reduce(self) -> int:
        """DisReduA to the global fixpoint; returns the rounds."""
        return self._rounds_to_fixpoint()

    def reduce_and_peel(self) -> int:
        """Reduce-and-peel until no vertex is undecided; returns the peels."""
        peels, remaining = 0, self._remaining()
        while remaining:
            self._rounds_to_fixpoint()
            self._peel()
            remaining = self._remaining()
            peels += 1
        return peels

    # ---------------------------------------------------------------- #
    # results in global ids
    # ---------------------------------------------------------------- #
    def result(self) -> dict:
        """Status and residual weight per vertex, offset, and the fold log
        as (kind, global v, global u) rows in each PE's log order."""
        status = np.empty(self.n, np.int64)
        w = np.empty(self.n, np.int64)
        logs, offset = [], 0
        for lg, st in zip(self.pes, self.states):
            status[lg.lo:lg.hi] = st.status[:lg.nloc]
            w[lg.lo:lg.hi] = st.w[:lg.nloc]
            kind, v, u = st.fold_log()
            logs.append(np.stack([kind, lg.gid[v], lg.gid[u]], axis=1))
            offset += st.offset
        return dict(status=status, w=w, offset=offset, log=logs)

    def members(self) -> np.ndarray:
        """Replay each PE's fold log newest-first from its included set."""
        out = np.zeros(self.n, bool)
        for lg, st in zip(self.pes, self.states):
            in_set = st.status == INCLUDED
            kind, v, u = st.fold_log()
            for k in range(kind.shape[0] - 1, -1, -1):
                if kind[k] == LOG_FOLD1:
                    in_set[v[k]] = not in_set[u[k]]
                else:
                    ent = lg.window[v[k]]
                    in_set[v[k]] = not (in_set[ent] & (lg.gid[ent] >= 0)).any()
            out[lg.lo:lg.hi] = in_set[:lg.nloc]
        return out


# -------------------------------------------------------------------- #
# checks that need no replay: what any answer must satisfy
# -------------------------------------------------------------------- #
def conflicts(indptr: np.ndarray, indices: np.ndarray,
              members: np.ndarray) -> int:
    """Edges of the input graph with both ends in ``members``."""
    src = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    return int((members[src] & members[indices]).sum()) // 2


def set_weight(weights: np.ndarray, members: np.ndarray) -> int:
    return int(np.asarray(weights, np.int64)[members].sum())
