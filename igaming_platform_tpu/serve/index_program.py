"""The device program of an index-mode RPC: one owner.

Every index RPC is ONE jitted program over the device-resident state:

    compose (gather the account's feature row by slot, write the four
    transaction columns, OR the blacklist flag) -> score
    [-> session head over the post-append window -> fold -> append]
    [-> drift sketch] [-> shadow re-score]

Two families: ``cached`` (feature table only) and ``session`` (table +
the per-account event ring, serve/session_state.py). Each has ONE body,
for one device and for a mesh; what differs is how a slot is reached
(:func:`slot_access`), chosen at trace time from whether the state is
slot-sharded. Sketch and shadow are the ``(sketch, shadow)`` variants of
the same body. :func:`build` is the only place these programs are jitted:
it holds their shardings for the three placements and what they donate.

Call forms, uniform over every ``(sketch, shadow)``::

    cached(params, cand, table, flags, chunk, thr)
      -> (packed[, sketch][, shadow_packed])
    session(params, sparams, table, flags, ring, cursor, length,
            chunk, thr, cand)
      -> (packed, ring', cursor', length'[, sketch][, shadow_packed])

``chunk`` is the launch's ONE host array: the padded rows' columns as
32-bit words, int32 [B, CHUNK_WORDS] (:func:`pack_chunk` writes it on
the host, :func:`unpack_chunk` reads it at the top of either body), so a
launch is one host-to-device transfer whatever the placement. ``thr``
lives on the device (placed when an operator sets it). ``packed`` is
int32 [5, B] (score, action, reason_mask, rule_score, ml_score bits);
``cand`` is the shadow candidate's param tree (None without one),
``sparams`` the session head's. Both are TRACED arguments, never
closure constants: a new candidate or a replaced head tree reuses the
compiled executables.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from igaming_platform_tpu.core.enums import SESSION_COLD_BIT, SESSION_PATTERN_BIT
from igaming_platform_tpu.core.features import F
from igaming_platform_tpu.models.ensemble import ML_HIGH_RISK_BIT, combine
from igaming_platform_tpu.obs.drift import sketch_kernel
from igaming_platform_tpu.parallel import state_sharding as ss
from igaming_platform_tpu.parallel.mesh import AXIS_DATA
from igaming_platform_tpu.serve.session_state import (
    EVENT_WIDTH,
    advance_counters,
    ring_append,
    ring_rows,
    windows_from_state,
)

_TX_COLS = (int(F.TX_AMOUNT), int(F.TX_TYPE_DEPOSIT),
            int(F.TX_TYPE_WITHDRAW), int(F.TX_TYPE_BET))

# The packed chunk's word layout, a row of int32 words a padded row: the
# table index, the within-batch occurrence rank, the amount's float32
# bits, the wire tx code, the flags, then the event's EVENT_WIDTH float32
# words by their bits. A pad row is all zeros but for its rank (pad rows
# all append to the scratch slot: distinct ranks keep them off each
# other), so it gathers slot 0, is scored and is discarded.
W_IDX, W_OCC, W_AMOUNT, W_TYPE, W_FLAGS, W_EVENT = range(6)
CHUNK_WORDS = W_EVENT + EVENT_WIDTH
FLAG_BLACKLIST = 1  # the request's blacklist bit
FLAG_REAL = 2       # a real row, not padding: the count of them is ``n``


def pack_chunk(shape: int, idxs, amounts, types, bl, occ=None) -> np.ndarray:
    """The host half of a launch: one FRESH int32 [shape, CHUNK_WORDS]
    array holding what the chunk's ids and wire columns decide (fresh by
    design: jax may alias host memory zero-copy on the CPU backend, so a
    pooled buffer could be read by an in-flight dispatch). The event
    words stay zero until ``session_state.encode_events_host`` writes
    them through :func:`chunk_events`; ``occ`` is the session family's
    (session_state.group_chunk)."""
    n = len(idxs)
    chunk = np.zeros((shape, CHUNK_WORDS), dtype=np.int32)  # noqa: MX04 — fresh per dispatch (zero-copy aliasing)
    chunk[:n, W_IDX] = idxs
    chunk.view(np.float32)[:n, W_AMOUNT] = amounts
    chunk[:n, W_TYPE] = types
    chunk[:n, W_FLAGS] = FLAG_REAL + FLAG_BLACKLIST * np.asarray(bl, dtype=bool)
    if occ is not None:
        chunk[:n, W_OCC] = occ
    chunk[n:, W_OCC] = np.arange(shape - n, dtype=np.int32)
    return chunk


def chunk_events(chunk: np.ndarray, n: int) -> np.ndarray:
    """The event words of the first ``n`` rows of a packed chunk as the
    float32 [n, EVENT_WIDTH] they are: a view, not a copy."""
    return chunk[:n, W_EVENT:].view(np.float32)


def chunk_columns(chunk: np.ndarray):
    """``(idxs, amounts, types)`` of a packed chunk as host views, for
    the split drift sketch (its own launch, obs/drift.py)."""
    return (chunk[:, W_IDX], chunk.view(np.float32)[:, W_AMOUNT],
            chunk[:, W_TYPE])


class Chunk(NamedTuple):
    """A packed chunk's columns on the device, bit for bit what the host
    wrote. ``real`` marks the rows that are not padding; ``n`` counts
    them."""

    idxs: Any
    occ: Any
    amounts: Any
    types: Any
    events: Any
    bl: Any
    real: Any
    n: Any


def unpack_chunk(chunk) -> Chunk:
    """The device half: slices and bitcasts of the one array, at the top
    of either family's body. The columns leave behind one fence, so the
    rest of the step is compiled as it was when each was an argument of
    its own (without it XLA re-plans a backbone step's VMEM around the
    slices: ``keye``'s and ``lfm2``'s normed positions went back to being
    copied out to HBM for the expert kernels, tests/test_chip_compile.py
    ``_assert_rows_come_in_by_the_kernel``)."""
    f32 = lambda w: jax.lax.bitcast_convert_type(w, jnp.float32)  # noqa: E731
    flags = chunk[:, W_FLAGS]
    real = (flags & FLAG_REAL) != 0
    return jax.lax.optimization_barrier(Chunk(
        idxs=chunk[:, W_IDX], occ=chunk[:, W_OCC],
        amounts=f32(chunk[:, W_AMOUNT]), types=chunk[:, W_TYPE],
        events=f32(chunk[:, W_EVENT:]), bl=(flags & FLAG_BLACKLIST) != 0,
        real=real, n=jnp.sum(real.astype(jnp.int32))))


def stack_packed(out: dict):
    """Canonical dict-output -> packed int32 [5, B] (score, action,
    reason_mask, rule_score, ml_score as IEEE-754 bits) — one D2H
    transfer instead of five."""
    return jnp.stack([
        out["score"].astype(jnp.int32),
        out["action"].astype(jnp.int32),
        out["reason_mask"].astype(jnp.int32),
        out["rule_score"].astype(jnp.int32),
        jax.lax.bitcast_convert_type(
            out["ml_score"].astype(jnp.float32), jnp.int32
        ),
    ])


def compose_rows(take, table, flags, idxs, amounts, types, bl):
    """The resident rows an index RPC scores: gather each account's
    feature row by slot (``take(arr, slots)`` is how a slot is read),
    write the per-transaction context (amount; deposit / withdraw / bet
    one-hots from the wire tx code) and OR the request's blacklist bit
    with the slot's flag. Pad rows index slot 0: scored and discarded.
    Returns ``(x [B, F], blv [B])``; ``flags=None`` composes rows nobody
    scores (the split drift sketch) and returns ``blv=None``."""
    txa, td, tw, tb = _TX_COLS
    x = take(table, idxs)
    f32 = x.dtype
    x = x.at[:, txa].set(amounts)
    x = x.at[:, td].set((types == 0).astype(f32))
    x = x.at[:, tw].set((types == 1).astype(f32))
    x = x.at[:, tb].set((types == 2).astype(f32))
    blv = None if flags is None else jnp.logical_or(bl, take(flags, idxs))
    return x, blv


class SlotAccess(NamedTuple):
    """How a body reaches per-slot state: the one thing that differs
    between one device (or a replicated mesh) and a slot-sharded mesh.
    ``rows`` is the number of ring slots this device holds."""

    take: Callable  # (arr, slots) -> the rows of a per-slot array
    ring: Callable  # (ring, slots, rows, n_events) -> stored [B, N, D]
    own: Callable   # (slots, rows, real) -> (append index, appended mask)


# One device holds every slot: a real row (not padding) is an owned row,
# and padding appends to the scratch slot ``capacity``.
_LOCAL = SlotAccess(
    take=lambda arr, slots: arr[slots],
    ring=lambda ring, slots, rows, n_events: ring_rows(ring, slots, n_events),
    own=lambda slots, rows, real: (slots, real),
)
# Inside a shard_map body over ``data``: reads are exact owner-select
# collectives; a row appends only on the shard that owns its slot
# (padding at ``capacity`` is nobody's and drops).
_SHARDED = SlotAccess(
    take=ss.gather_slots,
    ring=ss.gather_ring_slots,
    own=lambda slots, rows, real: ss.local_slot_index(rows, slots),
)


def slot_access(plan) -> SlotAccess:
    return _LOCAL if plan is None else _SHARDED


def epilogue(res: list, x, packed, n, sketch: bool, rescore):
    """[-> drift sketch] [-> shadow re-score] appended to a body's
    outputs: the sketch reduces the composed rows in-graph; ``rescore``
    (None without a shadow) scores the SAME rows with the candidate."""
    if sketch:
        res.append(sketch_kernel(x, packed, n))
    if rescore is not None:
        res.append(rescore())
    return tuple(res)


class SessionSpec(NamedTuple):
    """What the ``session`` family is built from: the head
    (models/session_heads.py) and the ring's sizes. Read by attribute: a
    SessionStateManager serves as one."""

    head_fn: Callable  # (sparams, window [B, N, D], lengths [B]) -> [B]
    capacity: int
    n_events: int
    min_events: int
    flag_threshold: float


def cached_body(score_fn, acc: SlotAccess, sketch: bool, shadow: bool):
    """The ``cached`` family's body: compose -> score [-> sketch]
    [-> shadow]."""

    def _cached_body(params, cand, table, flags, chunk, thr):
        c = unpack_chunk(chunk)
        x, blv = compose_rows(acc.take, table, flags, c.idxs, c.amounts,
                              c.types, c.bl)
        packed = stack_packed(score_fn(params, x, blv, thr))
        return epilogue(
            [packed], x, packed, c.n, sketch,
            (lambda: stack_packed(score_fn(cand, x, blv, thr)))
            if shadow else None)

    return _cached_body


def session_body(score_fn, cfg, spec, acc: SlotAccess, sketch: bool,
                 shadow: bool):
    """The ``session`` family's body: compose -> score -> session head
    over the post-append window -> fold -> in-place append [-> sketch]
    [-> shadow].

    The chunk's index column indexes the feature table; ``sidx``, derived
    from it here, the ring (pad rows -> ``capacity``: the scratch slot on
    one device, nobody's slot on a slot-sharded mesh); ``occ`` is the
    host-computed within-batch occurrence rank, so duplicate accounts
    append at distinct offsets.
    A row whose post-append window is WARM (>= ``min_events``) and whose
    head probability reaches ``flag_threshold`` has its ML component
    raised to it (``SESSION_PATTERN`` bit) and recombines through the
    ensemble rule; any other warm row is bit-identical to the
    session-off path; a COLD row never folds (``SESSION_COLD`` bit). The
    shadow branch folds the CANDIDATE's outputs the same way: promotion
    evidence is about the stateful program that would serve. The ring is
    flat and donated (session_state "the ring's at-rest layout"): a step
    moves O(batch) bytes whatever the capacity.
    """
    head_fn, capacity, n_events = spec.head_fn, spec.capacity, spec.n_events
    min_events, flag_threshold = spec.min_events, spec.flag_threshold

    def _session_fold(out, sprob, fold, cold, thr):
        """Fold one param tree's base outputs through the session head
        result — shared bit-for-bit by the production and the shadow
        branch (``sprob``/``fold``/``cold`` are params-independent)."""
        ml = out["ml_score"].astype(jnp.float32)
        ml2 = jnp.where(fold, jnp.maximum(ml, sprob), ml)
        # Recombine exactly as the base graph did (combine() is pure in
        # (rule, ml, mask)): strip the ML bit the base pass derived from
        # the un-folded ml, then let combine re-derive it from ml2 — a
        # non-folded row reproduces the base outputs bit-for-bit.
        mask_base = out["reason_mask"] & ~(1 << ML_HIGH_RISK_BIT)
        final, action, mask = combine(out["rule_score"], ml2, mask_base,
                                      cfg, thr)
        mask = mask | jnp.where(fold, 1 << SESSION_PATTERN_BIT, 0)
        mask = mask | jnp.where(cold, 1 << SESSION_COLD_BIT, 0)
        return stack_packed({
            "score": final, "action": action, "reason_mask": mask,
            "rule_score": out["rule_score"], "ml_score": ml2})

    # Named ``_body``: the benchmark finds this program in a trace by
    # ``^jit__body\(`` (chipbench/layer_metrics/device_step_ms.json).
    def _body(params, sparams, table, flags, ring, cursor, length, chunk,
              thr, cand):
        c = unpack_chunk(chunk)
        occ, events = c.occ, c.events
        sidx = jnp.where(c.real, c.idxs, capacity)
        x, blv = compose_rows(acc.take, table, flags, c.idxs, c.amounts,
                              c.types, c.bl)
        out = score_fn(params, x, blv, thr)

        # -- session head over the post-append window ---------------------
        #    Duplicate accounts within one batch see the BATCH-START state
        #    (batch-snapshot semantics — the host index and replay apply
        #    the same rule); their appends land at distinct cursor offsets.
        rows = cursor.shape[0]
        cur = acc.take(cursor, sidx)
        ln = acc.take(length, sidx)
        win, lp = windows_from_state(
            acc.ring(ring, sidx, rows, n_events), cur, ln, events, n_events)
        sprob = head_fn(sparams, win, lp).astype(jnp.float32)
        real = sidx < capacity
        warm = jnp.logical_and(lp >= min_events, real)
        fold = jnp.logical_and(warm, sprob >= flag_threshold)
        cold = jnp.logical_and(jnp.logical_not(warm), real)
        packed = _session_fold(out, sprob, fold, cold, thr)

        # -- in-place append (donated buffers: ring'/cursor'/length' alias
        #    their inputs) on the rows this device owns -------------------
        li, owned = acc.own(sidx, rows, real)
        ring2 = ring_append(ring, li, jnp.mod(cur + occ, n_events), events,
                            n_events)
        cursor2, length2 = advance_counters(cursor, length, li, ln, occ,
                                            owned, n_events)
        return epilogue(
            [packed, ring2, cursor2, length2], x, packed, c.n, sketch,
            (lambda: _session_fold(score_fn(cand, x, blv, thr), sprob, fold,
                                   cold, thr))
            if shadow else None)

    return _body


# What each positional argument and leading output of a family is, for
# placement: a param ``tree``, the feature ``table``, a 1-D per-``slot``
# array, the packed ``chunk`` (a row a padded row), a small ``repl``
# value, the ``packed`` [5, B] result.
_ARGS = {
    "cached": ("tree", "tree", "table", "slot", "chunk", "repl"),
    "session": ("tree", "tree", "table", "slot", "slot", "slot", "slot",
                "chunk", "repl", "tree"),
}
_OUTS = {"cached": ("packed",), "session": ("packed", "slot", "slot", "slot")}
# ring, cursor, length: outputs alias them; nothing else is donated.
_DONATE = {"cached": (), "session": (4, 5, 6)}


def build(score_fn, cfg, *, family: str, sketch: bool, shadow: bool,
          mesh, plan, session=None):
    """Jit one ``(family, sketch, shadow)`` program for where its state
    lives: one device (``mesh is None``), a replicated mesh, or a
    slot-sharded mesh (``plan``: parallel/state_sharding.SlotShardingPlan;
    the body runs inside ``shard_map`` and stays one dispatch).
    ``score_fn`` is the raw dict-output score graph; ``session`` is the
    ``session`` family's :class:`SessionSpec`."""
    if family not in _ARGS:
        raise ValueError(f"unknown index program family {family!r}")
    acc = slot_access(plan)
    if family == "session":
        body = session_body(score_fn, cfg, session, acc, sketch, shadow)
    else:
        body = cached_body(score_fn, acc, sketch, shadow)
    kinds_in = _ARGS[family]
    kinds_out = (_OUTS[family] + (("repl",) if sketch else ())
                 + (("packed",) if shadow else ()))
    donate = _DONATE[family]
    if plan is not None:
        spec = {"table": plan.spec(2), "slot": plan.spec(1)}
        return jax.jit(
            shard_map(
                body, mesh=plan.mesh,
                in_specs=tuple(spec.get(k, P()) for k in kinds_in),
                out_specs=tuple(spec.get(k, P()) for k in kinds_out),
                check_vma=False),
            donate_argnums=donate)
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        place = {
            "tree": None, "table": repl, "slot": repl, "repl": repl,
            "chunk": NamedSharding(mesh, P(AXIS_DATA, None)),
            "packed": NamedSharding(mesh, P(None, AXIS_DATA)),
        }
        return jax.jit(
            body,
            in_shardings=tuple(place[k] for k in kinds_in),
            out_shardings=tuple(place[k] for k in kinds_out),
            donate_argnums=donate)
    return jax.jit(body, donate_argnums=donate)


def warm_columns(shape: int) -> np.ndarray:
    """The dummy batch columns of one ladder shape, packed, for AOT
    warm-up: no row is real, so every row gathers slot 0 with tx type
    "other" and appends (session) to slot ``capacity`` at its own rank:
    no real account's window moves."""
    chunk = pack_chunk(shape, (), (), (), ())
    chunk[:, W_TYPE] = 4
    return chunk
