"""Batched conjunctive BM25 top-k over postings held on the device.

Role of the reference's per-document scoring loop (reference:
core/src/idx/ft/scorer.rs:13-92, Okapi BM25 with the lower-bounded idf,
k1=1.2 b=0.75) and of its posting-list intersection (termdocs.rs)
re-designed TPU-first: every rider of a launch (one `@@` statement with the
score order pushed down and a LIMIT) has its rarest term's postings tested
for membership in its other terms' postings, the survivors scored in
float32 and the k best returned, in ONE program whose shapes come from a
small fixed ladder and never from the data.

What is on the device, once a generation of the mirror
(idx/ft_mirror.py): the posting doc ids, term frequencies and each
posting's document length as one CSR triple, the document lengths over the
doc slots, and the most frequent terms again as dense tf rows over the doc
slots (the head). A rider brings its terms' ranges, head rows and idf values (a term look-up is host work) as
VALUES, all riders of a launch in one packed operand (`pack_riders`):
nothing of the corpus' statistics is baked into a program.

Two programs under one name, chosen by the static `slots`:

- `slots < doc slots` (the sparse steps: 1,024, 2,048, ... ): the rarest
  term's list is sliced from the CSR arrays ([slots], contiguous copies,
  its documents' lengths among them); a term outside the head has a list
  no longer than `slots`, sliced the same way and compared all against
  all; a term of the head is read from its dense row through a one-hot
  product on the MXU. No gather a candidate anywhere.
- `slots == doc slots` (the dense step, every term in the head): an
  elementwise pass over the terms' dense rows; no gather at all.

Ties go to the lower doc id in both (`lax.top_k` prefers the lower index,
and candidates stand in doc-id order), as the host route's stable sort has
them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLOTS_MIN = 1024  # the ladder's first step; also the least a posting or doc array is padded to
TERM_SLOTS = (2, 4, 8)  # term slots of the ladder; more terms take the host route
K_SLOTS = (16, 128, 1024)  # a statement's k = limit + start rides the smallest that holds it
RIDER_TILE = 8  # a launch carries 1 rider or tiles of 8
LANES = 128  # the doc slots are read as rows of 128 where a sparse step looks a candidate's slot up
_SENTINEL = np.iinfo(np.int32).max


def term_slots(terms: int):
    """The ladder's term slots for a query of `terms` terms, None past the last."""
    return next((t for t in TERM_SLOTS if terms <= t), None)


def k_slots(k: int):
    return next((s for s in K_SLOTS if k <= s), None)


def sparse_steps(longest: int):
    """The sparse steps a generation can be asked for: powers of two from
    SLOTS_MIN to the first that holds its longest list outside the head."""
    out, s = [SLOTS_MIN], SLOTS_MIN
    while s < longest:
        s *= 2
        out.append(s)
    return tuple(out)


def _slice_list(arrays, start, length, slots: int):
    """One posting list as [slots] slices of the posting-aligned `arrays`
    (the first the sorted doc ids): doc-id slots before the list read -1 and
    slots after it the sentinel, every other array 0 there (a slice that
    would run past the arrays' end is clamped back by `dynamic_slice`, so
    the list may start inside the window). Also the mask of the list."""
    s = jnp.clip(start, 0, arrays[0].shape[0] - slots)
    off = start - s
    i = jnp.arange(slots, dtype=jnp.int32)
    inside = (i >= off) & (i < off + length)
    d, *rest = (jax.lax.dynamic_slice(a, (s,), (slots,)) for a in arrays)
    d = jnp.where(i < off, -1, jnp.where(inside, d, _SENTINEL))
    return (d, *(jnp.where(inside, a, 0) for a in rest), inside)


def _term_score(tf, idf, norm, k1, dtype):
    """One term's BM25 share in `dtype`; `norm` = k1 (1 - b + b len / avg) and `k1` already in it."""
    tf = tf.astype(dtype)
    return idf.astype(dtype) * (tf * (k1 + 1)) / (tf + norm)


def _sparse_rider(dids, tfs, plens, head, slots, kk, dtype, starts, lens, rows, idf, nt, avg, k1, b):
    """No gather a candidate: the candidates, their tfs and their documents'
    lengths are three contiguous slices; a term outside the head is a slice
    as long, compared all against all; a term of the head is read through a
    one-hot product (the candidates' doc-slot rows of 128 picked out of the
    term's dense row on the MXU, exact: one 1 a row, integer tfs), then the
    lane by a compare. A per-candidate gather from HBM costs this chip ~80 ns
    an element, more than all of that (PERF.md section 6, PR 38)."""
    cand, tf0, dl, ok = _slice_list((dids, tfs, plens), starts[0], lens[0], slots)
    norm, k1 = (k1 * (1.0 - b + b * dl / avg)).astype(dtype), k1.astype(dtype)
    score = _term_score(tf0, idf[0], norm, k1, dtype)
    at = jnp.clip(cand, 0, head.shape[1] - 1)
    blocks = head.shape[1] // LANES
    # exact in bfloat16 while a tf fits a byte; wider tfs multiply in float32
    mm = jnp.bfloat16 if head.dtype == jnp.uint8 else jnp.float32
    in_block = ((at // LANES)[:, None] == jnp.arange(blocks, dtype=jnp.int32)[None, :]).astype(mm)
    in_lane = (at % LANES)[:, None] == jnp.arange(LANES, dtype=jnp.int32)[None, :]
    for j in range(1, starts.shape[0]):
        active = j < nt
        lst, ltf, _ = _slice_list((dids, tfs), starts[j], lens[j], slots)
        local = jnp.where(cand[:, None] == lst[None, :], ltf[None, :].astype(jnp.int32), 0).sum(axis=1)
        row = head[jnp.maximum(rows[j], 0)].reshape(blocks, LANES).astype(mm)
        picked = jnp.dot(in_block, row, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        dense = jnp.where(in_lane, picked, 0.0).sum(axis=1).astype(jnp.int32)
        tf = jnp.where(rows[j] >= 0, dense, local)
        ok = ok & ((tf > 0) | ~active)
        score = score + jnp.where(active, _term_score(tf, idf[j], norm, k1, dtype), 0)
    score = jnp.where(ok, score.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(score, kk)
    return vals, jnp.where(vals > -jnp.inf, cand[idx], -1), ok.sum(dtype=jnp.int32)


def _dense_rider(doclen, head, kk, dtype, rows, idf, nt, avg, k1, b):
    norm, k1 = (k1 * (1.0 - b + b * doclen / avg)).astype(dtype), k1.astype(dtype)
    ok = jnp.ones(doclen.shape, dtype=bool)
    score = jnp.zeros(doclen.shape, dtype=dtype)
    for j in range(rows.shape[0]):
        active = j < nt
        tf = head[jnp.maximum(rows[j], 0)].astype(jnp.int32)
        ok = ok & ((tf > 0) | ~active)
        score = score + jnp.where(active, _term_score(tf, idf[j], norm, k1, dtype), 0)
    score = jnp.where(ok, score.astype(jnp.float32), -jnp.inf)
    vals, idx = jax.lax.top_k(score, kk)
    return vals, jnp.where(vals > -jnp.inf, idx, -1), ok.sum(dtype=jnp.int32)


def pack_riders(payloads, riders: int, terms: int, k1: float, b: float) -> np.ndarray:
    """The riders of one launch as ONE int32 operand [riders, 4 * terms + 4]
    (one upload a launch, not eight: under eight sessions every hand-over
    to the runtime is a chance to lose the interpreter): a rider's `terms`
    range starts, lengths and head rows (-1 outside the head), its idf
    values' float32 bits, its term count (0: an empty lane), and the bits
    of its average document length, k1 and b. A payload is (starts, lens,
    rows, idf, term count, average length), rarest term first."""
    out = np.zeros((riders, 4 * terms + 4), dtype=np.int32)
    bits = out.view(np.float32)
    out[:, 2 * terms : 3 * terms] = -1
    bits[:, 4 * terms + 1 :] = (1.0, k1, b)
    for i, (starts, lens, rows, idf, nt, avg) in enumerate(payloads):
        out[i, :terms], out[i, terms : 2 * terms], out[i, 2 * terms : 3 * terms] = starts, lens, rows
        bits[i, 3 * terms : 4 * terms] = idf
        out[i, 4 * terms], bits[i, 4 * terms + 1] = nt, avg
    return out


def unpack_results(out, k: int):
    """(scores [R, k] f32, doc ids [R, k] int32, matched [R]) of a launch's one output."""
    out = np.asarray(out)
    return out[:, :k].view(np.float32), out[:, k : 2 * k], out[:, 2 * k]


@functools.partial(jax.jit, static_argnames=("slots", "k", "score_dtype"))
def bm25_and_topk(
    dids: jax.Array,  # [P] int32 posting doc ids, by (term, doc id); pad: the sentinel
    tfs: jax.Array,  # [P] posting term frequencies, the narrowest unsigned type that holds them
    plens: jax.Array,  # [P] f32 the length of each posting's document (what a sparse step reads in place of a gather)
    doclen: jax.Array,  # [D] f32 document lengths over the doc slots (0: no document); D a multiple of 128
    head: jax.Array,  # [H, D] tf of the H most frequent terms over the doc slots, in tfs' type
    riders: jax.Array,  # [R, 4 T + 4] int32: pack_riders
    *,
    slots: int,
    k: int,
    score_dtype: str = "float32",
):
    """-> [R, 2 k + 1] int32 (unpack_results): the float32 bits of the k
    best scores of the documents holding every term of each rider, best
    first, their doc ids, and how many matched; where fewer than k match,
    the rest read -inf and -1."""
    dtype, t = jnp.dtype(score_dtype), (riders.shape[1] - 4) // 4
    f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    starts, lens, rows = riders[:, :t], riders[:, t : 2 * t], riders[:, 2 * t : 3 * t]
    idf, nterms = f32(riders[:, 3 * t : 4 * t]), riders[:, 4 * t]
    avg, k1, b = (f32(riders[:, 4 * t + i]) for i in (1, 2, 3))
    if slots >= doclen.shape[0]:
        one = functools.partial(_dense_rider, doclen, head, k, dtype)
        vals, ids, matched = jax.vmap(one)(rows, idf, nterms, avg, k1, b)
    else:
        one = functools.partial(_sparse_rider, dids, tfs, plens, head, slots, k, dtype)
        vals, ids, matched = jax.vmap(one)(starts, lens, rows, idf, nterms, avg, k1, b)
    return jnp.concatenate([jax.lax.bitcast_convert_type(vals, jnp.int32), ids, matched[:, None]], axis=1)


def idf_of(doc_count: float, df) -> np.ndarray:
    """Upstream's lower-bounded idf in float64 (scorer.rs compute_bm25_score)."""
    n = max(float(doc_count), 1.0)
    df = np.asarray(df, dtype=np.float64)
    return np.log1p((n - df + 0.5) / (df + 0.5))


def bm25_scores_host(tf, df, doc_len, doc_count, total_len, k1=1.2, b=0.75):
    """The host route's scorer: [N, T] tfs of the AND-matched candidates ->
    [N] scores, in float64 NumPy, returned as float32."""
    n = max(float(doc_count), 1.0)
    avg_len = max(float(total_len) / n, 1e-6)
    tf = np.asarray(tf, dtype=np.float64)
    doc_len = np.asarray(doc_len, dtype=np.float64)
    norm = 1.0 - b + b * (doc_len[:, None] / avg_len)
    score = idf_of(n, df)[None, :] * (tf * (k1 + 1.0)) / (tf + k1 * norm)
    return score.sum(axis=1).astype(np.float32)


def graftcheck_sites():
    """Audit contract of the conjunctive top-k kernel (compile_log subsystem
    `bm25`, launched by idx/ft_mirror.py): a sparse step at each term-slot
    count and rider tile, and the dense step."""
    p, d, h = 8192, 2048, 8

    def build(shape):
        r, t = shape["riders"], shape["terms"]
        tf_dt = jnp.dtype(shape.get("tf_dtype", "uint8"))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
        args = (
            i32(p), jax.ShapeDtypeStruct((p,), tf_dt), f32(p), f32(d), jax.ShapeDtypeStruct((h, d), tf_dt),
            i32(r, 4 * t + 4),
        )
        return functools.partial(bm25_and_topk, slots=shape["slots"], k=shape["k"]), args

    shapes = [
        {"label": f"s{s}_t{t}_r{r}_k16", "slots": s, "terms": t, "riders": r, "k": 16}
        for s, t, r in ((1024, 2, 1), (1024, 4, RIDER_TILE), (1024, 8, RIDER_TILE), (d, 4, 1), (d, 8, RIDER_TILE))
    ] + [{"label": "s1024_t4_r8_k128_u16", "slots": 1024, "terms": 4, "riders": RIDER_TILE, "k": 128,
          "tf_dtype": "uint16"}]
    return [
        {
            "subsystem": "bm25",
            "module": __name__,
            "kind": "single",
            "allowed_collectives": (),
            # one packed output: score bits, doc ids, the matched count
            "out_dtypes": ("int32",),
            "shapes": shapes,
            "build": build,
        }
    ]
