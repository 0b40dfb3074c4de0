"""Incremental RGA store — steady-state collaborative editing on device.

The one-shot kernel (antidote_tpu/mat/rga_kernel.py) re-merges the whole
op log per call: O(history) per edit burst, unusable for a living
document (the reference's RGA materializes incrementally inside its
gen_server; SURVEY §5.7 names the long-log case a first-class target).
This store splits the document into

- a **base**: the stable prefix, materialized once into a frozen
  preorder (uid, parent-uid, element, live flag, subtree extent), and
- a **window**: the unstable op tail, kept as dense op lanes.

Reads merge only the window — O(window · log) for the tree/rank work —
then splice each window subtree into the base by binary search and
assemble the document with one O(doc) sort.  Steady-state cost drops
from "re-run the full multi-round merge over all history" to "tiny
merge + one sort", and the fold (the only full-history pass) amortizes
over its GC cadence.  The splice is exact RGA order, not an
approximation: a window vertex anchored at base vertex V must sit among
V's already-folded children in uid-descending order, so the base keeps a
child-search index sorted by ``(parent_uid, uid desc)`` and the splice
position for a root with uid *u* is the preorder position of V's first
child with uid < u (else the end of V's subtree).  Sibling-order
correctness against folded siblings is exactly what naive
"append-after-anchor" schemes get wrong.

Folding (at a stability threshold, the GST analogue) runs the full
merge ONCE over base + newly-stable window ops — tombstones keep their
rows (they remain splice anchors) but drop their live flag — and
rebuilds the preorder/search arrays; the window compacts to its
unstable suffix.  Fold cost is O(doc) but amortized at GC cadence, like
the reference's ``?OPS_THRESHOLD`` materializer GC.

Stability gives the two invariants the split relies on (same GST
contract as the OR-Set store, mat/store.py):
- causal closure: a stable vertex's parent is stable (or base), so the
  stable set folds as whole subtrees hanging off the base;
- no stable op is still in flight, so folded positions are final.

All shapes are static (PB base rows, NW window lanes, MD delete lanes);
capacity growth is a host-side repack.  Window and delete lanes carry
FULL commit vector clocks (origin column, commit time, snapshot VC
columns), so a read materializes exactly the snapshot's inclusion set —
``op in snapshot iff commit_vc(op) <= read_vc`` (the reference
materializer rule, src/materializer.erl:101-106) — and the fold horizon
is the gossiped dense GST, the same contract as the OR-Set store
(mat/store.py orset_gc).  Reads below the folded base are the caller's
log-replay case (DevicePlane ReadBelowBase).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from antidote_tpu.clocks import dense
from antidote_tpu.mat import ingest, rga_kernel
from antidote_tpu.obs.prof import kernel_span
from antidote_tpu.mat.rga_kernel import _I32MAX, pack_uid

_I64MAX = jnp.iinfo(jnp.int64).max


@dataclass
class RgaStoreState:
    """Device arrays for one RGA document (a pytree).

    Base rows sit in document preorder; ``bsort_*`` is the uid-sorted
    view for lookups and ``ckey/cpos`` the (parent, uid-desc) child
    index for splices.  ``actor_bits`` is the uid packing width."""

    # base, in preorder (padding rows: buid = _I32MAX)
    buid: jax.Array       # int32[PB] packed uids
    bparent: jax.Array    # int32[PB] parent uid (0 = document head)
    belem: jax.Array      # int32[PB]
    blive: jax.Array      # bool[PB] (False = tombstone kept as anchor)
    bsub_end: jax.Array   # int32[PB] preorder index one past the subtree
    bn: jax.Array         # int32[] used rows
    # uid-sorted base view
    bsort_uid: jax.Array  # int32[PB]
    bsort_pos: jax.Array  # int32[PB] preorder index of that uid
    # child-search index, sorted by packed (parent_uid, uid desc)
    ckey: jax.Array       # int64[PB]
    cpos: jax.Array       # int32[PB]
    # window op lanes
    wlam: jax.Array       # int32[NW]
    wact: jax.Array       # int32[NW]
    wrlam: jax.Array      # int32[NW] left-neighbour ref (0 = head)
    wract: jax.Array      # int32[NW]
    welem: jax.Array      # int32[NW]
    wdc: jax.Array        # int32[NW] origin DC column
    wct: jax.Array        # int64[NW] commit time
    wss: jax.Array        # int64[NW, D] snapshot VC columns
    wn: jax.Array         # int32[]
    # pending delete lanes
    dlam: jax.Array       # int32[MD]
    dact: jax.Array       # int32[MD]
    ddc: jax.Array        # int32[MD]
    dct: jax.Array        # int64[MD]
    dss: jax.Array        # int64[MD, D]
    dn: jax.Array         # int32[]
    actor_bits: int

    @property
    def pb(self) -> int:
        return self.buid.shape[0]

    @property
    def nw(self) -> int:
        return self.wlam.shape[0]

    @property
    def md(self) -> int:
        return self.dlam.shape[0]

    @property
    def d(self) -> int:
        return self.wss.shape[1]


jax.tree_util.register_dataclass(
    RgaStoreState,
    data_fields=["buid", "bparent", "belem", "blive", "bsub_end", "bn",
                 "bsort_uid", "bsort_pos", "ckey", "cpos",
                 "wlam", "wact", "wrlam", "wract", "welem",
                 "wdc", "wct", "wss", "wn",
                 "dlam", "dact", "ddc", "dct", "dss", "dn"],
    meta_fields=["actor_bits"],
)


def rga_store_init(pb: int, nw: int, md: int, n_dcs: int = 1,
                   actor_bits: int = 8) -> RgaStoreState:
    i32 = lambda shape, fill=0: jnp.full(shape, fill, jnp.int32)
    i64 = lambda shape, fill=0: jnp.full(shape, fill, jnp.int64)
    return RgaStoreState(
        buid=i32((pb,), _I32MAX), bparent=i32((pb,)), belem=i32((pb,)),
        blive=jnp.zeros((pb,), bool), bsub_end=i32((pb,)),
        bn=jnp.zeros((), jnp.int32),
        bsort_uid=i32((pb,), _I32MAX), bsort_pos=i32((pb,)),
        ckey=jnp.full((pb,), _I64MAX, jnp.int64), cpos=i32((pb,)),
        wlam=i32((nw,)), wact=i32((nw,)), wrlam=i32((nw,)),
        wract=i32((nw,)), welem=i32((nw,)),
        wdc=i32((nw,)), wct=i64((nw,)), wss=i64((nw, n_dcs)),
        wn=jnp.zeros((), jnp.int32),
        dlam=i32((md,)), dact=i32((md,)),
        ddc=i32((md,)), dct=i64((md,)), dss=i64((md, n_dcs)),
        dn=jnp.zeros((), jnp.int32),
        actor_bits=actor_bits,
    )


def _ckey_pack(parent_uid, uid):
    """int64 child-search key: (parent asc, uid desc)."""
    return ((parent_uid.astype(jnp.int64) << 32)
            | (jnp.int64(_I32MAX) - uid.astype(jnp.int64)))


@kernel_span("mat.rga")
@partial(jax.jit, donate_argnums=(0,))
def rga_append(st: RgaStoreState, ins_lamport, ins_actor, ref_lamport,
               ref_actor, elem, ins_dc, ins_ct, ins_ss,
               del_lamport, del_actor, del_dc, del_ct, del_ss,
               n_ins=None, n_del=None):
    """Append one op block (B insert lanes + C delete lanes) into the
    window, each lane carrying its full commit VC (origin column,
    commit time, snapshot columns).  Returns (state, ok) — ok=False
    means the window or delete lanes are full: the caller folds (or
    grows) and retries.

    ``n_ins``/``n_del`` are the LOGICAL lane counts when the arrays
    are padded to a dispatch bucket (rga_append_padded): the padded
    tail is written into the invalid region beyond wn/dn — masked by
    every fold/read and overwritten by the next append — while the
    counters advance by the logical counts only.  Without bucketing,
    every distinct (B, C) pair mints its own XLA program (measured
    ~0.45 s/block on CPU: the whole config-4 steady-state deficit)."""
    b = ins_lamport.shape[0]
    c = del_lamport.shape[0]
    nb = b if n_ins is None else n_ins
    nc = c if n_del is None else n_del
    # physical room for the PADDED block: the dynamic_update_slice
    # below would clamp its start (corrupting valid lanes) if the pad
    # overhung — refuse conservatively, the caller folds/grows
    ok = (st.wn + b <= st.nw) & (st.dn + c <= st.md)
    i32 = lambda a: a.astype(jnp.int32)
    i64 = lambda a: a.astype(jnp.int64)

    def put_at(dst, src, n, cast):
        zero = jnp.zeros((), n.dtype)
        start = (jnp.where(ok, n, zero),) + (zero,) * (dst.ndim - 1)
        upd = jax.lax.dynamic_update_slice(dst, cast(src), start)
        return jnp.where(ok, upd, dst)

    put = lambda dst, src: put_at(dst, src, st.wn, i32)
    put64 = lambda dst, src: put_at(dst, src, st.wn, i64)
    putd = lambda dst, src: put_at(dst, src, st.dn, i32)
    putd64 = lambda dst, src: put_at(dst, src, st.dn, i64)

    return replace(
        st,
        wlam=put(st.wlam, ins_lamport), wact=put(st.wact, ins_actor),
        wrlam=put(st.wrlam, ref_lamport), wract=put(st.wract, ref_actor),
        welem=put(st.welem, elem),
        wdc=put(st.wdc, ins_dc), wct=put64(st.wct, ins_ct),
        wss=put64(st.wss, ins_ss),
        wn=jnp.where(ok, st.wn + nb, st.wn),
        dlam=putd(st.dlam, del_lamport), dact=putd(st.dact, del_actor),
        ddc=putd(st.ddc, del_dc), dct=putd64(st.dct, del_ct),
        dss=putd64(st.dss, del_ss),
        dn=jnp.where(ok, st.dn + nc, st.dn),
    ), ok


def _append_bucket(n: int, floor: int = 64) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def rga_append_padded(st: RgaStoreState, ins_cols, del_cols,
                      floor: int = 64):
    """:func:`rga_append` with both lane blocks padded to power-of-two
    buckets and the logical counts passed through — callers whose
    block sizes vary per call (the live plane's per-commit groups, the
    bench's lamport-sliced deletes) compile a handful of programs
    instead of one per distinct size.  ``ins_cols``/``del_cols`` are
    the positional argument tuples of rga_append (host arrays)."""
    b = int(np.asarray(ins_cols[0]).shape[0])
    c = int(np.asarray(del_cols[0]).shape[0])
    bp, cp = _append_bucket(b, floor), _append_bucket(c, floor)

    def pad(a, n):
        a = np.asarray(a)
        if a.shape[0] == n:
            return jnp.asarray(a)
        w = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return jnp.asarray(np.pad(a, w))

    return rga_append(
        st, *(pad(a, bp) for a in ins_cols),
        *(pad(a, cp) for a in del_cols), n_ins=b, n_del=c)


#: packed-append column layout (shared by insert AND delete rows so
#: one [bp+cp, 7+D] tensor carries both sections): [lam, act, rlam,
#: ract, elem, dc, ct, ss(D)] — delete rows use the same lam/act/dc/
#: ct/ss positions and leave rlam/ract/elem zero
_PK_LAM, _PK_ACT, _PK_RLAM, _PK_RACT, _PK_ELEM, _PK_DC, _PK_CT, \
    _PK_NSCAL = 0, 1, 2, 3, 4, 5, 6, 7


@kernel_span("mat.rga")
@partial(jax.jit, donate_argnums=(0,), static_argnames=("bp",))
def rga_append_packed(st: RgaStoreState, packed, bp, n_ins, n_del):
    """:func:`rga_append` fed from ONE packed tensor: rows ``[:bp]``
    are the (padded) insert lanes, rows ``[bp:]`` the delete lanes,
    columns per ``_PK_*``.  The split is static (``bp`` is the insert
    bucket), so the upload that used to be 13 per-column transfers is
    a single H2D — the coalesced-ingest economy (mat/ingest.py) on the
    RGA steady window."""
    d = st.d
    i32 = lambda a: a.astype(jnp.int32)
    ins = packed[:bp]
    dl = packed[bp:]
    return rga_append(
        st,
        i32(ins[:, _PK_LAM]), i32(ins[:, _PK_ACT]),
        i32(ins[:, _PK_RLAM]), i32(ins[:, _PK_RACT]),
        i32(ins[:, _PK_ELEM]), i32(ins[:, _PK_DC]),
        ins[:, _PK_CT], ins[:, _PK_NSCAL:_PK_NSCAL + d],
        i32(dl[:, _PK_LAM]), i32(dl[:, _PK_ACT]), i32(dl[:, _PK_DC]),
        dl[:, _PK_CT], dl[:, _PK_NSCAL:_PK_NSCAL + d],
        n_ins=n_ins, n_del=n_del)


def rga_append_coalesced(st: RgaStoreState, ins_cols, del_cols,
                         floor: int = 64):
    """:func:`rga_append_padded`'s bucketing with the coalesced-ingest
    upload contract: both lane blocks pack into ONE host tensor and
    ONE H2D (vs 13 per-column uploads), counted in the INGEST_*
    metrics.  Same argument tuples and return as rga_append_padded —
    the legacy form stays as the benches' comparison baseline."""
    b = int(np.asarray(ins_cols[0]).shape[0])
    c = int(np.asarray(del_cols[0]).shape[0])
    bp, cp = _append_bucket(b, floor), _append_bucket(c, floor)
    d = st.d
    packed = np.zeros((bp + cp, _PK_NSCAL + d), dtype=np.int64)
    for j, a in enumerate(ins_cols[:_PK_NSCAL]):
        packed[:b, j] = np.asarray(a)
    packed[:b, _PK_NSCAL:] = np.asarray(ins_cols[_PK_NSCAL])
    dl = packed[bp:]
    for j, a in zip((_PK_LAM, _PK_ACT, _PK_DC, _PK_CT), del_cols[:4]):
        dl[:c, j] = np.asarray(a)
    dl[:c, _PK_NSCAL:] = np.asarray(del_cols[4])
    st, ok = rga_append_packed(st, packed, bp=bp, n_ins=b, n_del=c)
    ingest.note_dispatch(b + c, packed.nbytes)
    return st, ok


def _included(ss, dc, ct, rv):
    """bool[N]: commit_vc(op) <= rv columnwise (the materializer
    inclusion rule over dense lanes)."""
    cvc = dense.commit_vc(ss, dc, ct)
    return jnp.all(cvc <= rv[None, :].astype(jnp.int64), axis=1)


@kernel_span("mat.rga")
@jax.jit
def rga_read(st: RgaStoreState, read_vc):
    """Materialize the full RGA state at dense snapshot ``read_vc``
    (int64[D]): merge the snapshot-included window forest and splice it
    into the base preorder.  Returns ``(lam, act, elem, vis, n)`` —
    int32[PB+NW] arrays in document order INCLUDING tombstones (vis
    False), n = number of present vertices — i.e. exactly the host
    oracle's state tuple (crdt/rga.py), so downstream generation can
    read this reconstruction (positions index visible vertices; lamport
    max ranges over all).  Requires read_vc >= the fold horizon (the
    caller's ReadBelowBase contract): every base row is in-snapshot by
    construction."""
    nw, pb = st.nw, st.pb
    bits = st.actor_bits
    lanes = jnp.arange(nw, dtype=jnp.int32)
    winc = _included(st.wss, st.wdc, st.wct, read_vc)
    in_window = (lanes < st.wn) & winc

    wuid = pack_uid(st.wlam, st.wact, bits)
    # park invalid lanes, duplicates of base rows, and in-window dups
    in_base = _bsearch_hit(st.bsort_uid, wuid)[0]
    wuid = jnp.where(in_window & ~in_base, wuid, _I32MAX)
    by_uid = jnp.argsort(wuid)
    sorted_uid = wuid[by_uid]
    dup_sorted = jnp.concatenate(
        [jnp.zeros((1,), bool), sorted_uid[1:] == sorted_uid[:-1]])
    dup = jnp.zeros((nw,), bool).at[by_uid].set(dup_sorted)
    wuid = jnp.where(dup, _I32MAX, wuid)
    valid = wuid != _I32MAX

    ref = pack_uid(st.wrlam, st.wract, bits)
    # parent resolution: window first, then base anchor, else parked
    wpos = jnp.searchsorted(sorted_uid, ref)
    wcp = jnp.clip(wpos, 0, nw - 1)
    whit = (wpos < nw) & (sorted_uid[wcp] == ref) & ~dup[by_uid[wcp]]
    parent_w = by_uid[wcp]
    bhit, bidx = _bsearch_hit(st.bsort_uid, ref)
    is_root = valid & ~whit & (bhit | (ref == 0))
    parked_v = valid & ~whit & ~is_root
    valid = valid & ~parked_v  # unresolvable: excluded with subtree

    parked = nw  # sentinel vertex
    # segment key: real parent / unique per root / parked bucket
    parent_key = jnp.where(
        whit & valid, parent_w,
        jnp.where(is_root, nw + 1 + lanes, parked))

    rank, reachable, root_of, fin_ok = _window_tour(
        parent_key, wuid, valid, is_root, nw)

    # splice position for each root (gathered for every vertex via
    # root_of): first base child of the anchor with uid < root uid,
    # else the end of the anchor's subtree (head anchors end at bn)
    q = _ckey_pack(ref, wuid)
    ci = jnp.searchsorted(st.ckey, q)
    cic = jnp.clip(ci, 0, pb - 1)
    chit = (ci < pb) & ((st.ckey[cic] >> 32) == ref.astype(jnp.int64))
    anchor_pos = st.bsort_pos[bidx]
    sub_end = jnp.where(
        ref == 0, st.bn, st.bsub_end[jnp.clip(anchor_pos, 0, pb - 1)])
    splice = jnp.where(chit, st.cpos[cic], sub_end)       # [NW] (roots)

    # pending deletes: hide window and base targets (snapshot-included
    # deletes only — a tombstone newer than the read snapshot must not
    # hide its target yet)
    duid = pack_uid(st.dlam, st.dact, bits)
    dvalid = (jnp.arange(st.md, dtype=jnp.int32) < st.dn) \
        & _included(st.dss, st.ddc, st.dct, read_vc)
    dwp = jnp.searchsorted(sorted_uid, duid)
    dwc = jnp.clip(dwp, 0, nw - 1)
    dwhit = dvalid & (dwp < nw) & (sorted_uid[dwc] == duid)
    deleted_w = jnp.zeros((nw,), bool).at[
        jnp.where(dwhit, by_uid[dwc], nw)].set(True, mode="drop")
    dbhit, dbidx = _bsearch_hit(st.bsort_uid, duid)
    hidden_b = jnp.zeros((pb,), bool).at[
        jnp.where(dvalid & dbhit, st.bsort_pos[dbidx], pb)
    ].set(True, mode="drop")

    bpos_arr = jnp.arange(pb, dtype=jnp.int32)
    # presence = in the RGA state (tombstones included, as the host
    # oracle keeps them); visibility = live and not hidden at snapshot
    present_b = bpos_arr < st.bn
    present_w = reachable
    visible_w = present_w & ~deleted_w
    visible_b = st.blive & present_b & ~hidden_b

    # final order: (splice_pos, tier, uid desc among roots, tour rank)
    rshift = max(1, (2 * (nw + 1)).bit_length())
    ruid = wuid[root_of]
    w_primary = (splice[root_of].astype(jnp.int64) << 1)
    b_primary = (bpos_arr.astype(jnp.int64) << 1) | 1
    w_secondary = ((jnp.int64(_I32MAX) - ruid.astype(jnp.int64))
                   << rshift) | rank.astype(jnp.int64)
    primary = jnp.concatenate([
        jnp.where(present_b, b_primary, _I64MAX),
        jnp.where(present_w, w_primary, _I64MAX)])
    secondary = jnp.concatenate(
        [jnp.zeros((pb,), jnp.int64), w_secondary])
    perm = rga_kernel._lexsort2(primary, secondary)
    mask32 = (1 << bits) - 1
    lam_all = jnp.concatenate(
        [(st.buid >> bits) & (_I32MAX >> bits), st.wlam])
    act_all = jnp.concatenate([st.buid & mask32, st.wact])
    elems = jnp.concatenate([st.belem, st.welem])
    present = jnp.concatenate([present_b, present_w])[perm]
    vis = jnp.concatenate([visible_b, visible_w])[perm] & present
    lam = jnp.where(present, lam_all[perm], 0)
    act = jnp.where(present, act_all[perm], 0)
    elem_out = jnp.where(present, elems[perm], 0)
    n = jnp.sum(present).astype(jnp.int32)
    return lam, act, elem_out, vis, n


@kernel_span("mat.rga")
@jax.jit
def rga_read_doc(st: RgaStoreState, read_vc):
    """Visible document only: (doc int32[PB+NW] padded with -1,
    n_visible) — the bench-facing view over :func:`rga_read`."""
    lam, act, elem, vis, _n = rga_read(st, read_vc)
    order = jnp.argsort(~vis, stable=True)
    n_vis = jnp.sum(vis).astype(jnp.int32)
    doc = jnp.where(jnp.arange(vis.shape[0]) < n_vis,
                    elem[order], -1)
    return doc, n_vis


def _bsearch_hit(sorted_arr, q):
    """(hit bool[...], index) of q in a sorted int array."""
    n = sorted_arr.shape[0]
    p = jnp.searchsorted(sorted_arr, q)
    c = jnp.clip(p, 0, n - 1)
    return (p < n) & (sorted_arr[c] == q), c


def _window_tour(parent_key, uid, valid, is_root, nw):
    """Euler tour + Wyllie rank over the window forest.  Returns
    (rank, reachable, root_of, fin) — rank orders vertices within their
    subtree (tour distance: order-exact, not dense)."""
    parked = nw
    sperm = rga_kernel._lexsort2(parent_key, -uid)
    sparent = parent_key[sperm]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sparent[1:] != sparent[:-1]])
    fc_idx = jnp.where(first, sparent, 2 * nw + 3)
    first_child = jnp.full((nw + 1,), -1, jnp.int32).at[fc_idx].set(
        sperm.astype(jnp.int32), mode="drop")
    same = sparent[:-1] == sparent[1:]
    ns_src = jnp.where(same, sperm[:-1], 2 * nw + 5)
    next_sib = jnp.full((nw,), -1, jnp.int32).at[ns_src].set(
        sperm[1:].astype(jnp.int32), mode="drop")

    up = nw + 1
    s = 2 * (nw + 1)
    v = jnp.arange(nw + 1, dtype=jnp.int32)
    fc = first_child[v]
    succ_down = jnp.where(fc >= 0, fc, up + v)
    ns = jnp.concatenate([next_sib, jnp.full((1,), -1, jnp.int32)])
    pk = jnp.concatenate(
        [parent_key.astype(jnp.int32), jnp.full((1,), parked, jnp.int32)])
    # non-root, non-parked: up -> next sib | parent's up.  pk < nw is a
    # real parent; roots/parked handled below
    par_clip = jnp.clip(pk, 0, nw)
    succ_up = jnp.where(ns[v] >= 0, ns[v], up + par_clip[v])
    root_mask = jnp.concatenate([is_root, jnp.zeros((1,), bool)])
    succ_up = jnp.where(root_mask, up + v, succ_up)  # terminal self-loop
    parked_mask = jnp.concatenate(
        [~valid, jnp.ones((1,), bool)])  # incl. sentinel vertex
    succ_down = jnp.where(parked_mask, v, succ_down)
    succ_up = jnp.where(parked_mask, up + v, succ_up)
    succ = jnp.concatenate([succ_down, succ_up])

    slot = jnp.arange(s, dtype=jnp.int32)
    dist = (succ != slot).astype(jnp.int32)
    steps = max(1, (s - 1).bit_length())

    def body(_, c):
        d, nx = c
        return d + d[nx], nx[nx]

    dist, fin = jax.lax.fori_loop(0, steps, body, (dist, succ))
    vw = jnp.arange(nw, dtype=jnp.int32)
    # reachable iff the chain terminates at an anchored root's up-slot
    is_root_up = jnp.concatenate(
        [jnp.zeros((nw + 1,), bool), root_mask])
    term = fin[vw]
    reachable = valid & is_root_up[jnp.clip(term, 0, s - 1)]
    root_of = jnp.clip(term - up, 0, nw - 1)
    rank = dist[root_of] - dist[vw]          # 0 at the root, tour order
    rank = jnp.where(reachable, rank, 0)
    return rank, reachable, root_of, fin


@kernel_span("mat.rga")
@partial(jax.jit, donate_argnums=(0,), static_argnames=())
def rga_fold(st: RgaStoreState, gst):
    """Fold window ops whose commit VC <= the dense GST (int64[D]) into
    the base: one full merge over base + stable window (the amortized
    GC; tombstoned vertices keep their rows as anchors), then compact
    the window to its unstable suffix.  Requires the folded base to fit
    PB rows (the host wrapper grows first; see rga_fold_host)."""
    nw, pb, md = st.nw, st.pb, st.md
    bits = st.actor_bits
    mask32 = (1 << bits) - 1

    lanes = jnp.arange(nw, dtype=jnp.int32)
    in_window = lanes < st.wn
    stable_w = in_window & _included(st.wss, st.wdc, st.wct, gst)
    # duplicate deliveries of base rows must not re-enter the merge (a
    # kept window copy would shadow the base row's tombstone flag);
    # they are dropped from the window instead
    wuid_w = pack_uid(st.wlam, st.wact, bits)
    base_dup = in_window & _bsearch_hit(st.bsort_uid, wuid_w)[0]
    stable_w = stable_w & ~base_dup
    dlanes = jnp.arange(md, dtype=jnp.int32)
    stable_d = (dlanes < st.dn) & _included(st.dss, st.ddc, st.dct, gst)

    bpos = jnp.arange(pb, dtype=jnp.int32)
    in_base = bpos < st.bn
    blam = (st.buid >> bits).astype(jnp.int32)
    bact = (st.buid & mask32).astype(jnp.int32)
    bplam = (st.bparent >> bits).astype(jnp.int32)
    bpact = (st.bparent & mask32).astype(jnp.int32)

    ins_lam = jnp.concatenate([jnp.where(in_base, blam, 0), st.wlam])
    ins_act = jnp.concatenate([jnp.where(in_base, bact, 0), st.wact])
    ref_lam = jnp.concatenate([bplam, st.wrlam])
    ref_act = jnp.concatenate([bpact, st.wract])
    elem = jnp.concatenate([st.belem, st.welem])
    valid = jnp.concatenate([in_base, stable_w])
    prev_live = jnp.concatenate(
        [st.blive, jnp.ones((nw,), bool)])

    r = rga_kernel.rga_merge_full(
        ins_lam, ins_act, ref_lam, ref_act, elem, valid,
        st.dlam, st.dact, stable_d, actor_bits=bits)

    t = pb + nw
    rank = jnp.where(r["reachable"], r["rank"], _I32MAX)
    perm = jnp.argsort(rank)
    n_new = jnp.sum(r["reachable"]).astype(jnp.int32)
    live = prev_live & ~r["deleted"]
    parent = r["parent"]
    parent_uid = jnp.where(
        parent >= t, 0,
        r["uid"][jnp.clip(parent, 0, t - 1)]).astype(jnp.int32)

    take = lambda a: a[perm][:pb]
    reach_s = take(r["reachable"])
    new_pos = jnp.arange(pb, dtype=jnp.int32)
    buid = jnp.where(reach_s, take(r["uid"]).astype(jnp.int32), _I32MAX)
    bparent = jnp.where(reach_s, take(parent_uid), 0)
    belem = jnp.where(reach_s, take(elem), 0)
    blive = reach_s & take(live)
    bsub_end = jnp.where(
        reach_s, new_pos + take(r["subtree"]), 0)

    sort_perm = jnp.argsort(buid)
    bsort_uid = buid[sort_perm]
    bsort_pos = new_pos[sort_perm]

    ck = jnp.where(reach_s.astype(jnp.int64) > 0,
                   _ckey_pack(bparent, buid), _I64MAX)
    ck_perm = jnp.argsort(ck)
    ckey = ck[ck_perm]
    cpos = new_pos[ck_perm]

    # compact the window to the unstable suffix (stable order
    # preserved); folded ops and base duplicates both drop
    keep_w = in_window & ~stable_w & ~base_dup
    worder = jnp.argsort(~keep_w, stable=True)
    wn_new = jnp.sum(keep_w).astype(jnp.int32)
    def _compact(order, n_new, size):
        def go(a):
            live = jnp.arange(size) < n_new
            m = live.reshape((size,) + (1,) * (a.ndim - 1))
            return jnp.where(m, a[order], 0)
        return go

    cw = _compact(worder, wn_new, nw)
    keep_d = (dlanes < st.dn) & ~stable_d
    dorder = jnp.argsort(~keep_d, stable=True)
    dn_new = jnp.sum(keep_d).astype(jnp.int32)
    cd = _compact(dorder, dn_new, md)

    return replace(
        st,
        buid=buid, bparent=bparent, belem=belem, blive=blive,
        bsub_end=bsub_end, bn=n_new,
        bsort_uid=bsort_uid, bsort_pos=bsort_pos, ckey=ckey, cpos=cpos,
        wlam=cw(st.wlam), wact=cw(st.wact), wrlam=cw(st.wrlam),
        wract=cw(st.wract), welem=cw(st.welem),
        wdc=cw(st.wdc), wct=cw(st.wct), wss=cw(st.wss),
        wn=wn_new,
        dlam=cd(st.dlam), dact=cd(st.dact),
        ddc=cd(st.ddc), dct=cd(st.dct), dss=cd(st.dss),
        dn=dn_new,
    ), n_new


def rga_grow(st: RgaStoreState, pb: int | None = None,
             nw: int | None = None, md: int | None = None,
             n_dcs: int | None = None) -> RgaStoreState:
    """Host-side capacity regrade (never shrinks); rare."""
    pb = max(pb or st.pb, st.pb)
    nw = max(nw or st.nw, st.nw)
    md = max(md or st.md, st.md)
    d = max(n_dcs or st.d, st.d)
    if (pb, nw, md, d) == (st.pb, st.nw, st.md, st.d):
        return st

    def pad(a, n, fill=0):
        a = np.asarray(a)
        return jnp.asarray(np.pad(a, (0, n - len(a)),
                                  constant_values=fill))

    def pad2(a, n, cols):
        a = np.asarray(a)
        return jnp.asarray(np.pad(
            a, ((0, n - a.shape[0]), (0, cols - a.shape[1]))))

    return RgaStoreState(
        buid=pad(st.buid, pb, _I32MAX), bparent=pad(st.bparent, pb),
        belem=pad(st.belem, pb), blive=pad(st.blive, pb, False),
        bsub_end=pad(st.bsub_end, pb), bn=st.bn,
        bsort_uid=pad(st.bsort_uid, pb, _I32MAX),
        bsort_pos=pad(st.bsort_pos, pb),
        ckey=pad(st.ckey, pb, int(_I64MAX)), cpos=pad(st.cpos, pb),
        wlam=pad(st.wlam, nw), wact=pad(st.wact, nw),
        wrlam=pad(st.wrlam, nw), wract=pad(st.wract, nw),
        welem=pad(st.welem, nw),
        wdc=pad(st.wdc, nw), wct=pad(st.wct, nw),
        wss=pad2(st.wss, nw, d), wn=st.wn,
        dlam=pad(st.dlam, md), dact=pad(st.dact, md),
        ddc=pad(st.ddc, md), dct=pad(st.dct, md),
        dss=pad2(st.dss, md, d), dn=st.dn,
        actor_bits=st.actor_bits,
    )


def rga_remap_actors(st: RgaStoreState, perm) -> RgaStoreState:
    """Rewrite every packed actor id through ``perm`` (int32[2^bits],
    old id -> new id, 0 -> 0) and re-derive the base order.

    Needed because sibling order is uid-DESC and the host oracle breaks
    lamport ties by ACTOR STRING: the device's interned ids must order
    like the strings, so when a new actor arrives that does not sort
    after all existing ones, the owner re-interns in sorted order and
    remaps the document (actors per document are few — DC/node ids — so
    this is rare and bounded).  The base preorder depends on sibling
    order, hence the re-merge via a zero-horizon fold after the id
    rewrite."""
    bits = st.actor_bits
    mask = (1 << bits) - 1
    pm = jnp.asarray(perm, jnp.int32)

    def remap_uid(uid_arr):
        out = ((uid_arr >> bits) << bits) | pm[uid_arr & mask]
        return jnp.where(uid_arr == _I32MAX, _I32MAX, out)

    buid = remap_uid(st.buid)
    bparent = remap_uid(st.bparent)
    pos = jnp.arange(st.pb, dtype=jnp.int32)
    sort_perm = jnp.argsort(buid)
    in_base = pos < st.bn
    ck = jnp.where(in_base.astype(jnp.int64) > 0,
                   _ckey_pack(bparent, buid), _I64MAX)
    ck_perm = jnp.argsort(ck)
    st = replace(
        st,
        buid=buid, bparent=bparent,
        bsort_uid=buid[sort_perm], bsort_pos=pos[sort_perm],
        ckey=ck[ck_perm], cpos=pos[ck_perm],
        wact=pm[st.wact], wract=pm[st.wract], dact=pm[st.dact],
    )
    # zero-horizon fold: folds nothing from the window (commit times are
    # positive) but re-merges the base rows, rebuilding the preorder and
    # subtree extents under the remapped sibling order
    st, _bn = rga_fold(st, jnp.zeros((st.d,), jnp.int64))
    return st


def rga_fold_host(st: RgaStoreState, gst) -> RgaStoreState:
    """Host wrapper around :func:`rga_fold`: grows the base first when
    the folded document might not fit (worst case bn + stable window).
    ``gst`` is the dense stable VC (int64[D]); a scalar is treated as a
    single-column horizon for the simulation benches."""
    gst = np.asarray(gst, dtype=np.int64).reshape(-1)
    if gst.shape[0] != st.d:
        gst = np.pad(gst, (0, st.d - gst.shape[0]))
    need = int(st.bn) + int(st.wn)
    if need > st.pb:
        new_pb = st.pb
        while new_pb < need:
            new_pb *= 2
        st = rga_grow(st, pb=new_pb)
    st, _bn = rga_fold(st, gst)
    return st
