"""The plain reference the benchmark holds the ordering service to.

Written without any code of the program, so no change to the program can
move it:

* ``csr`` builds the symmetric adjacency of a graph from its edges.
* ``opc`` is the fill of the Cholesky factor under an ordering: the
  operation count ``OPC = sum_c n_c**2`` over the columns c of L, with
  n_c the nonzeros of column c, diagonal included (Gilbert-Ng-Peyton
  column counts over the elimination tree, as in CSparse ``cs_counts``).
* ``nested_dissection`` is a plain George-Liu nested dissection: a
  level-structure separator from a pseudo-peripheral vertex, refined by
  sequential vertex-separator Fiduccia-Mattheyses passes, with leaves of
  at most ``leaf_size`` vertices ordered by exact minimum degree.  Its
  balance sums and gains are computed in ``dtype``: ``float32``, as the
  program computes them, or ``bfloat16`` for the control, where every
  addition rounds and a running sum of unit weights stops at 256.

Orderings use the program's convention: ``perm[k]`` is the vertex
eliminated k-th.
"""
from __future__ import annotations

import numpy as np

LEAF_SIZE = 96
FM_PASSES = 3
EPS = 0.12
#: a side of the top split holds at least n / PIECE of the n vertices
PIECE = 200


def csr(n: int, edges: np.ndarray):
    """Symmetric CSR ``(xadj, adjncy)`` of an undirected edge list,
    without self loops or repeated edges."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    key = np.unique(lo * n + hi)
    lo, hi = key // n, key % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    xadj = np.zeros(n + 1, dtype=np.int64)
    np.add.at(xadj, src + 1, 1)
    return np.cumsum(xadj), dst


def is_permutation(perm, n: int) -> bool:
    p = np.asarray(perm)
    if p.ndim != 1 or p.shape[0] != n or not np.issubdtype(p.dtype,
                                                            np.integer):
        return False
    return n == 0 or (p.min() >= 0 and p.max() < n
                      and bool((np.bincount(p, minlength=n) == 1).all()))


# ------------------------------------------------------------------ #
# fill
# ------------------------------------------------------------------ #
def _etree(xadj, adjncy, perm, iperm):
    n = len(perm)
    parent = [-1] * n
    ancestor = [-1] * n
    for i in range(n):
        v = perm[i]
        for u in adjncy[xadj[v]:xadj[v + 1]]:
            k = iperm[u]
            if k >= i:
                continue
            j = k
            while ancestor[j] != -1 and ancestor[j] != i:
                nxt = ancestor[j]
                ancestor[j] = i
                j = nxt
            if ancestor[j] == -1:
                ancestor[j] = i
                parent[j] = i
    return parent


def _postorder(parent):
    n = len(parent)
    head = [-1] * n
    nxt = [-1] * n
    for v in range(n - 1, -1, -1):
        p = parent[v]
        if p >= 0:
            nxt[v] = head[p]
            head[p] = v
    post = []
    for root in range(n):
        if parent[root] != -1:
            continue
        stack = [root]
        while stack:
            v = stack[-1]
            c = head[v]
            if c != -1:
                head[v] = nxt[c]
                stack.append(c)
            else:
                post.append(v)
                stack.pop()
    return post


def col_counts(xadj, adjncy, perm) -> np.ndarray:
    """Nonzeros of each column of the Cholesky factor of the matrix
    permuted by ``perm`` (column order = elimination order)."""
    perm = [int(v) for v in perm]
    n = len(perm)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    xadj = [int(x) for x in xadj]
    adjncy = [int(x) for x in adjncy]
    iperm = [0] * n
    for k, v in enumerate(perm):
        iperm[v] = k
    parent = _etree(xadj, adjncy, perm, iperm)
    post = _postorder(parent)
    first = [-1] * n
    delta = [0] * n
    for k in range(n):
        j = post[k]
        delta[j] = 1 if first[j] == -1 else 0
        while j != -1 and first[j] == -1:
            first[j] = k
            j = parent[j]
    maxfirst = [-1] * n
    prevleaf = [-1] * n
    ancestor = list(range(n))
    for k in range(n):
        j = post[k]
        if parent[j] != -1:
            delta[parent[j]] -= 1
        v = perm[j]
        for u in adjncy[xadj[v]:xadj[v + 1]]:
            i = iperm[u]
            if i <= j or first[j] <= maxfirst[i]:
                continue
            maxfirst[i] = first[j]
            jprev = prevleaf[i]
            prevleaf[i] = j
            if jprev == -1:
                delta[j] += 1
            else:
                q = jprev
                while q != ancestor[q]:
                    q = ancestor[q]
                s = jprev
                while s != q:
                    sp = ancestor[s]
                    ancestor[s] = q
                    s = sp
                delta[j] += 1
                delta[q] -= 1
        if parent[j] != -1:
            ancestor[j] = parent[j]
    counts = delta[:]
    for k in range(n):
        j = post[k]
        if parent[j] != -1:
            counts[parent[j]] += counts[j]
    return np.asarray(counts, dtype=np.int64)


def opc(xadj, adjncy, perm) -> float:
    """Operation count of the Cholesky factorization under ``perm``."""
    c = col_counts(xadj, adjncy, perm).astype(np.float64)
    return float((c * c).sum())


def top_imbalance(xadj, adjncy, perm) -> float:
    """Imbalance of the top separator that ``perm`` implies.

    In the elimination tree of an ordering that eliminates a separator
    after the parts it separates, the separator is the chain down from
    the root, and the parts hang below its lowest vertex.  The reading
    is ``|w0 - w1| / n`` with ``w0`` the largest subtree hanging there
    and ``w1`` the rest of the vertices below the chain: the measure of
    the balance tolerance that dissection's FM keeps (``EPS``).
    """
    perm = [int(v) for v in perm]
    n = len(perm)
    if n == 0:
        return 0.0
    iperm = [0] * n
    for k, v in enumerate(perm):
        iperm[v] = k
    parent = _etree([int(x) for x in xadj], [int(x) for x in adjncy],
                    perm, iperm)
    kids = [[] for _ in range(n + 1)]   # n: a root above the roots
    size = [1] * n
    for j in range(n):                  # a parent comes after its child
        p = parent[j]
        kids[n if p < 0 else p].append(j)
        if p >= 0:
            size[p] += size[j]
    v, chain = n, 0
    while len(kids[v]) == 1:
        v = kids[v][0]
        chain += 1
    below = [size[c] for c in kids[v]]
    w0 = max(below, default=0)
    w1 = n - chain - w0
    return abs(w0 - w1) / n


def split_imbalance(xadj, adjncy, perm) -> float:
    """Imbalance of the top split that ``perm`` implies, read from the
    order itself.

    Dissection orders one part, then the other, then the separator, and
    no edge joins the two parts.  The reading takes the shortest tail of
    ``perm``, of at most ``n / 2`` vertices, whose removal leaves a
    prefix that some position cuts into two sides with no edge between
    them, and is ``|w0 - w1| / n`` at the most balanced such cut.  A
    part may hold several components, ordered one after another, so the
    cut between the parts is not the only one.  A side must hold at
    least ``n / PIECE`` vertices: a smaller one is a piece that a few
    separator vertices cut off whole, such as a vertex whose neighbours
    all lie in the separator, and where those few come last they free it
    before the rest of the separator frees the parts.  With no such tail
    it reads 1.0.

    ``top_imbalance`` reads the same where the separator's vertices form
    one chain in the elimination tree.  Where a separator vertex has no
    neighbour among the vertices ordered before it but later separator
    vertices, as FM leaves on circuits, the chain branches inside the
    separator and ``top_imbalance`` counts nearly every vertex as one
    side; this reading does not depend on the tree.
    """
    xadj = np.asarray(xadj, dtype=np.int64)
    adjncy = np.asarray(adjncy, dtype=np.int64)
    n = len(xadj) - 1
    if n == 0:
        return 0.0
    iperm = np.empty(n, dtype=np.int64)
    iperm[np.asarray(perm, dtype=np.int64)] = np.arange(n)
    # lo[j]: the earliest position joined by an edge to position j
    lo = np.arange(n)
    np.minimum.at(lo, iperm[np.repeat(np.arange(n), np.diff(xadj))],
                  iperm[adjncy])
    # reach[t]: the smallest j whose edge (lo[j], j) crosses the cut
    # before position t (n where none does): the cut is free in every
    # prefix of at most reach[t] positions
    reach = np.full(n, n, dtype=np.int64)
    for j in range(n - 1, -1, -1):      # the smallest j is written last
        reach[lo[j] + 1:j + 1] = j

    def sides_hold(t, L):
        return PIECE * np.minimum(t, L - t) >= n

    # cut t counts in a prefix of L positions where it is free there
    # (t < L <= reach[t]), the tail n - L is at most n / 2, and both
    # sides hold their share of n: the longest such L is reach[t]
    cuts = np.arange(1, n)
    cuts = cuts[(reach[cuts] > cuts) & (n - reach[cuts] <= n // 2)
                & sides_hold(cuts, reach[cuts])]
    if not len(cuts):
        return 1.0
    longest = int(reach[cuts].max())
    at = np.arange(1, longest)
    at = at[(reach[at] >= longest) & sides_hold(at, longest)]
    return float(np.abs(2 * at - longest).min()) / n


# ------------------------------------------------------------------ #
# ordering
# ------------------------------------------------------------------ #
def _subgraph(xadj, adjncy, vs, loc):
    """Local CSR of the subgraph induced by the sorted vertex ids ``vs``;
    ``loc`` maps global ids to local ones and is -1 elsewhere."""
    loc[vs] = np.arange(len(vs))
    starts, ends = xadj[vs], xadj[vs + 1]
    lens = ends - starts
    idx = (np.repeat(starts - np.cumsum(lens) + lens, lens)
           + np.arange(lens.sum()))
    nb = loc[adjncy[idx]]
    src = np.repeat(np.arange(len(vs)), lens)
    keep = nb >= 0
    loc[vs] = -1
    sx = np.zeros(len(vs) + 1, dtype=np.int64)
    np.add.at(sx, src[keep] + 1, 1)
    return np.cumsum(sx), nb[keep]


def _neighbors_of(sx, sadj, front):
    starts, ends = sx[front], sx[front + 1]
    lens = ends - starts
    idx = (np.repeat(starts - np.cumsum(lens) + lens, lens)
           + np.arange(lens.sum()))
    return sadj[idx]


def _bfs_levels(sx, sadj, root):
    n = len(sx) - 1
    lvl = np.full(n, -1, dtype=np.int64)
    lvl[root] = 0
    front = np.array([root])
    d = 0
    while len(front):
        nb = np.unique(_neighbors_of(sx, sadj, front))
        nb = nb[lvl[nb] < 0]
        d += 1
        lvl[nb] = d
        front = nb
    return lvl


def _components(sx, sadj):
    n = len(sx) - 1
    comp = np.full(n, -1, dtype=np.int64)
    c = 0
    for v in range(n):
        if comp[v] >= 0:
            continue
        comp[_bfs_levels(sx, sadj, v) >= 0] = c
        c += 1
    return comp, c


def _pseudo_peripheral(sx, sadj):
    deg = np.diff(sx)
    root = int(np.argmin(deg))
    lvl = _bfs_levels(sx, sadj, root)
    for _ in range(8):
        last = np.nonzero(lvl == lvl.max())[0]
        cand = int(last[np.argmin(deg[last])])
        lv2 = _bfs_levels(sx, sadj, cand)
        if lv2.max() <= lvl.max():
            break
        root, lvl = cand, lv2
    return root, lvl


def _wsum(x, dtype):
    """Sum in ``dtype``, one rounded addition at a time."""
    acc = dtype(0)
    for v in np.asarray(x, dtype=dtype):
        acc = dtype(acc + v)
    return acc


def _level_split(sx, sadj, lvl, dtype):
    """Part vector (0, 1, 2 = separator) from the level set at which the
    running weight first reaches half the total."""
    h = int(lvl.max())
    by_level = np.argsort(lvl, kind="stable")
    run = np.cumsum(np.ones(len(lvl), dtype=dtype), dtype=dtype)
    half = dtype(run[-1] / dtype(2))
    cut = int(lvl[by_level[int(np.argmax(run >= half))]])
    cut = min(max(cut, 1), h - 1)
    part = np.where(lvl < cut, 0, 1).astype(np.int8)
    on = np.nonzero(lvl == cut)[0]
    up = _neighbors_of(sx, sadj, on)
    src = np.repeat(on, np.diff(sx)[on])
    touches = np.zeros(len(lvl), dtype=bool)
    touches[src[lvl[up] == cut + 1]] = True
    part[on] = np.where(touches[on], 2, 0)
    return part


def _ell(sx, sadj):
    n = len(sx) - 1
    deg = np.diff(sx)
    d = max(int(deg.max()), 1)
    nbr = np.full((n, d), -1, dtype=np.int64)
    cols = np.arange(len(sadj)) - np.repeat(sx[:-1], deg)
    nbr[np.repeat(np.arange(n), deg), cols] = sadj
    return nbr


def _fm(nbr, part, dtype, passes=FM_PASSES, eps=EPS):
    """Sequential vertex-separator FM; balance sums and gains in ``dtype``.

    A move takes a separator vertex to one side and pulls its neighbours
    on the other side into the separator.  Each pass makes at most
    ``2 |S| + 16`` moves, hill-climbing, and keeps the state with the
    lightest separator whose imbalance stays within the bound.
    """
    n = len(part)
    w = np.ones(n, dtype=dtype)
    valid = nbr >= 0
    safe = np.where(valid, nbr, 0)

    def sums(p):
        return (_wsum(w[p == 0], dtype), _wsum(w[p == 1], dtype),
                _wsum(w[p == 2], dtype))

    w0, w1, ws = sums(part)
    eps_abs = dtype(dtype(eps) * dtype(dtype(w0 + w1) + ws))
    bound = max(eps_abs, dtype(abs(w0 - w1)))
    for _ in range(passes):
        moved = np.zeros(n, dtype=bool)
        best = (float(ws), float(abs(w0 - w1)), part.copy(), w0, w1, ws)
        budget = 2 * int((part == 2).sum()) + 16
        for _ in range(budget):
            cand = np.nonzero((part == 2) & ~moved)[0]
            if not len(cand):
                break
            pn = np.where(valid[cand], part[safe[cand]], 3)
            wn = np.where(valid[cand], w[safe[cand]], dtype(0))
            p0 = np.sum(np.where(pn == 1, wn, dtype(0)), axis=1, dtype=dtype)
            p1 = np.sum(np.where(pn == 0, wn, dtype(0)), axis=1, dtype=dtype)
            wc = w[cand]
            imb = dtype(abs(w0 - w1))
            lim = max(eps_abs, imb)
            imb0 = np.abs((w0 + wc) - (w1 - p0)).astype(dtype)
            imb1 = np.abs((w0 - p1) - (w1 + wc)).astype(dtype)
            g0 = np.where(imb0 <= lim, (wc - p0).astype(np.float64), -np.inf)
            g1 = np.where(imb1 <= lim, (wc - p1).astype(np.float64), -np.inf)
            s0 = g0 - 1e-6 * imb0.astype(np.float64)
            s1 = g1 - 1e-6 * imb1.astype(np.float64)
            scores = np.concatenate([s0, s1])
            i = int(np.argmax(scores))
            if scores[i] == -np.inf:
                break
            side = 0 if i < len(cand) else 1
            v = int(cand[i % len(cand)])
            nv = nbr[v][valid[v]]
            pulled = nv[part[nv] == 1 - side]
            wp = _wsum(w[pulled], dtype)
            part[pulled] = 2
            part[v] = side
            moved[v] = True
            if side == 0:
                w0, w1 = dtype(w0 + w[v]), dtype(w1 - wp)
            else:
                w0, w1 = dtype(w0 - wp), dtype(w1 + w[v])
            ws = dtype(dtype(ws - w[v]) + wp)
            imb_now = float(abs(w0 - w1))
            if imb_now <= float(bound) and (
                    float(ws) < best[0]
                    or (float(ws) == best[0] and imb_now < best[1])):
                best = (float(ws), imb_now, part.copy(), w0, w1, ws)
        part, w0, w1, ws = best[2], best[3], best[4], best[5]
    return part


def _min_degree(sx, sadj):
    """Exact minimum degree by explicit elimination (ties by local id)."""
    n = len(sx) - 1
    adj = [set(int(u) for u in sadj[sx[v]:sx[v + 1]]) for v in range(n)]
    alive = set(range(n))
    order = []
    while alive:
        v = min(alive, key=lambda x: (len(adj[x]), x))
        nb = adj[v]
        for u in nb:
            adj[u].discard(v)
            adj[u] |= nb - {u}
        alive.discard(v)
        order.append(v)
    return np.asarray(order, dtype=np.int64)


def _separate(sx, sadj, dtype):
    """Part vector of one dissection step, or None when the subgraph is
    too shallow to cut."""
    _, lvl = _pseudo_peripheral(sx, sadj)
    if lvl.max() < 2:
        return None
    part = _level_split(sx, sadj, lvl, dtype)
    refined = _fm(_ell(sx, sadj), part.copy(), dtype)
    if (refined == 0).any() and (refined == 1).any():
        return refined
    return part


def nested_dissection(xadj, adjncy, dtype=np.float32,
                      leaf_size: int = LEAF_SIZE,
                      separator_last: bool = True) -> np.ndarray:
    """Plain nested dissection ordering of the graph ``(xadj, adjncy)``.

    ``separator_last=False`` breaks the guarantee that makes dissection
    worth having, and is the benchmark's control: each separator is then
    eliminated before the two parts it separates.
    """
    xadj = np.asarray(xadj, dtype=np.int64)
    adjncy = np.asarray(adjncy, dtype=np.int64)
    n = len(xadj) - 1
    perm = np.empty(n, dtype=np.int64)
    loc = np.full(n, -1, dtype=np.int64)
    stack = [(np.arange(n, dtype=np.int64), 0)]
    while stack:
        vs, start = stack.pop()
        if not len(vs):
            continue
        sx, sadj = _subgraph(xadj, adjncy, vs, loc)
        if len(vs) <= leaf_size:
            perm[start:start + len(vs)] = vs[_min_degree(sx, sadj)]
            continue
        comp, nc = _components(sx, sadj)
        if nc > 1:
            for c in range(nc):
                part_vs = vs[comp == c]
                stack.append((part_vs, start))
                start += len(part_vs)
            continue
        part = _separate(sx, sadj, dtype)
        if part is None:
            perm[start:start + len(vs)] = vs[_min_degree(sx, sadj)]
            continue
        a, b, s = vs[part == 0], vs[part == 1], vs[part == 2]
        if separator_last:
            perm[start + len(a) + len(b):start + len(vs)] = s
        else:
            perm[start:start + len(s)] = s
            start += len(s)
        stack.append((a, start))
        stack.append((b, start + len(a)))
    return perm
