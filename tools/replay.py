"""Deterministic decision replay — re-score the ledger bit-exact.

# analysis: replay-path

``python -m tools.replay --dir <LEDGER_DIR>`` reads every
:class:`DecisionRecord` from a decision-ledger WAL (serve/ledger.py),
rebuilds the pinned scoring stack, re-scores each record from its
feature snapshot, and diffs the outputs BIT-EXACT — score, action,
reason mask, rule score, and the ml score's IEEE-754 bits. Decisions
taken in the DEGRADED_CPU_HEURISTIC tier replay through the SAME
conservative scorer (serve/supervisor.heuristic_scores), so a chaos
window's answers are provable, not just available. The verdict is the
``replay`` block of the ledger drill's artifact (tools/drills/soak.py).

Pinned checkpoint: by default the repo's seeded convention (multitask
params from ``jax.random.key(0)``, the same init every serving harness
and fleet replica resolves); ``--checkpoint`` restores an Orbax
checkpoint instead. Either way the replay params' fingerprint must match
the fingerprint recorded on each device/host-tier decision — a mismatch
is counted and fails the verdict, never silently re-scored against the
wrong model.

``--verify`` is the self-contained smoke (``make replay-verify``): score
a seeded batch under a CHAOS_PLAN (ledger-append faults included), then
replay the resulting ledger and require zero mismatches.

This is a replay-path module: analyzer rule CC06 bans wall-clock reads
and unseeded RNG here — replay derives everything from recorded values.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_COMPARE_FIELDS = ("score", "action", "reason_mask", "rule_score",
                   "ml_score_bits")


def _resolve_params(backend: str, checkpoint: str | None):
    """The pinned checkpoint: an explicit Orbax path, else the repo's
    seeded init convention for the backend."""
    if checkpoint:
        from igaming_platform_tpu.train.checkpoint import (
            restore_params_for_serving,
        )

        return {"multitask": restore_params_for_serving(checkpoint)}
    if backend == "multitask":
        import jax

        from igaming_platform_tpu.models.multitask import init_multitask

        return {"multitask": jax.device_get(init_multitask(jax.random.key(0)))}
    return None


class _EngineCache:
    """One warmed engine per (backend, params fingerprint) — replay
    groups share it. Fingerprints are resolved to param trees in order:
    the pinned checkpoint/seeded convention, then the PARAMS VAULT the
    promotion controller writes (``<ledger_dir>/params-vault/<fp>``) —
    so decisions scored by a promoted candidate replay bit-exact against
    the exact tree that scored them, across the promotion boundary."""

    def __init__(self, batch: int, checkpoint: str | None,
                 vault_dir: str | None = None):
        self.batch = batch
        self.checkpoint = checkpoint
        self.vault_dir = vault_dir
        self._engines: dict[tuple[str, str], object] = {}

    def _build(self, backend: str, params):
        from igaming_platform_tpu.core.config import (
            BatcherConfig,
            ScoringConfig,
        )
        from igaming_platform_tpu.serve.scorer import TPUScoringEngine

        return TPUScoringEngine(
            ScoringConfig(),
            ml_backend=backend,
            params=params,
            batcher_config=BatcherConfig(batch_size=self.batch,
                                         max_wait_ms=1.0),
            # Replay engines re-score recorded snapshots; session windows
            # are verified separately (verify_session_chain) from ledger
            # event order, never by mutating live session state here.
            session_state=False,
        )

    def get_for(self, backend: str, fp: str):
        """Engine whose params fingerprint equals ``fp``, or None when no
        params source (pinned convention or vault) resolves it."""
        eng = self._engines.get((backend, fp))
        if eng is not None:
            return eng
        pinned = self._build(backend, _resolve_params(backend, self.checkpoint))
        if pinned.params_fingerprint == fp:
            self._engines[(backend, fp)] = pinned
            return pinned
        pinned.close()
        if self.vault_dir:
            from igaming_platform_tpu.train.promote import vault_load

            params = vault_load(self.vault_dir, fp)
            if params is not None:
                eng = self._build(backend, params)
                if eng.params_fingerprint != fp:
                    # A tampered/corrupt vault entry must fail loudly,
                    # never silently re-score against the wrong model.
                    eng.close()
                    raise RuntimeError(
                        f"params vault entry {fp} restored to fingerprint "
                        f"{eng.params_fingerprint} — vault corrupt")
                self._engines[(backend, fp)] = eng
                return eng
        return None

    def close(self) -> None:
        for eng in self._engines.values():
            eng.close()


def _replay_compiled(engine, records) -> list[dict]:
    """Re-score feature-snapshot records through the engine's compiled
    step (same ladder padding, one packed readback per chunk); returns
    the recomputed field dict per record."""
    import jax

    from igaming_platform_tpu.serve.scorer import _unpack_host

    out_rows: list[dict] = []
    for lo in range(0, len(records), engine.batch_size):
        chunk = records[lo:lo + engine.batch_size]
        x = np.stack([r.features for r in chunk]).astype(np.float32)
        bl = np.array([r.blacklisted for r in chunk], dtype=bool)
        out, n = engine.launch_packed(x, bl)
        host = _unpack_host(jax.device_get(out))
        bits = np.ascontiguousarray(host["ml_score"], np.float32).view(np.uint32)
        for i in range(n):
            out_rows.append({
                "score": int(host["score"][i]),
                "action": int(host["action"][i]),
                "reason_mask": int(host["reason_mask"][i]),
                "rule_score": int(host["rule_score"][i]),
                "ml_score_bits": int(bits[i]),
            })
    return out_rows


def _replay_heuristic(records, thresholds) -> list[dict]:
    from igaming_platform_tpu.serve.supervisor import heuristic_scores

    x = np.stack([r.features for r in records]).astype(np.float32)
    bl = np.array([r.blacklisted for r in records], dtype=bool)
    out = heuristic_scores(x, bl, np.asarray(thresholds, np.int32))
    bits = np.ascontiguousarray(out["ml_score"], np.float32).view(np.uint32)
    return [{
        "score": int(out["score"][i]),
        "action": int(out["action"][i]),
        "reason_mask": int(out["reason_mask"][i]),
        "rule_score": int(out["rule_score"][i]),
        "ml_score_bits": int(bits[i]),
    } for i in range(len(records))]


def _recorded_fields(r) -> dict:
    return {
        "score": r.score,
        "action": r.action,
        "reason_mask": r.reason_mask,
        "rule_score": r.rule_score,
        "ml_score_bits": r.ml_score_bits,
    }


# ---------------------------------------------------------------------------
# Stateful decisions: session-window reconstruction + hash verification


def verify_session_chain(records, *, max_samples: int = 10,
                         twin_keep: int = 64) -> dict:
    """Reconstruct every session-scored decision's post-append window
    from LEDGER EVENT ORDER alone and verify its ``session_state_hash``
    bit-exact (serve/session_state.py is the other side of the
    contract).

    ``records`` is the WAL-ordered decision stream. Consecutive records
    sharing a decision-batch prefix form one CHUNK — one fused dispatch,
    one batch-snapshot append unit: every row's window is computed from
    the chunk-start twin state (duplicate accounts included), then all
    events commit in row order, exactly as the serving side did.

    The recorded per-account event sequence number makes the pass
    self-synchronizing: ``seq == 1`` with a non-empty twin means the
    server lost its session index (SIGKILL restart / engine rebuild) —
    the twin resets and verification continues. A forward seq jump is a
    chain gap (a dropped ledger row): counted, that row unverifiable,
    the twin resyncs at the recorded seq. Eviction never resets the
    chain — the host session index survives it by design.
    """
    from igaming_platform_tpu.serve.session_state import (
        encode_events_host,
        window_hash,
    )
    from igaming_platform_tpu.serve.wire import TX_TYPE_CODES

    twins: dict[str, dict] = {}
    stats = {
        "session_records": 0, "session_verified": 0,
        "session_hash_mismatch": 0, "session_chain_gaps": 0,
        "session_resets": 0, "session_reordered": 0,
        "session_mismatch_samples": [],
    }

    def _twin(acct: str) -> dict:
        tw = twins.get(acct)
        if tw is None:
            tw = {"events": [], "seq": 0, "last_ts": 0.0}
            twins[acct] = tw
        return tw

    def flush_chunk(chunk) -> None:
        # Batch-start snapshot per account. A chunk whose first
        # occurrence for an account carries seq == 1 against a non-empty
        # chain is a server-side session-index reset (SIGKILL restart /
        # engine rebuild): the snapshot truncates and the chain follows.
        snap: dict[str, dict] = {}
        occ: dict[str, int] = {}
        for rec in chunk:
            a = rec.account_id
            if a not in snap:
                tw = _twin(a)
                s = {"events": list(tw["events"]), "seq": tw["seq"],
                     "last_ts": tw["last_ts"], "reset": False}
                if rec.session_seq == 1 and tw["seq"] != 0:
                    stats["session_resets"] += 1
                    s = {"events": [], "seq": 0, "last_ts": 0.0,
                         "reset": True}
                snap[a] = s
        # Verify every row against the snapshot (batch semantics), while
        # computing the event row it contributes.
        committed: list = []  # (account_id, event, seq, ts)
        for rec in chunk:
            stats["session_records"] += 1
            s = snap[rec.account_id]
            k = occ.get(rec.account_id, 0)
            occ[rec.account_id] = k + 1
            expected = s["seq"] + k + 1
            dt = (0.0 if s["seq"] == 0
                  else max(0.0, rec.ts_unix - s["last_ts"]))
            code = TX_TYPE_CODES.get(rec.tx_type, 4)
            event = encode_events_host([rec.amount], [code], [dt])[0]
            committed.append((rec.account_id, event, rec.session_seq,
                              rec.ts_unix))
            hist = rec.session_len - 1
            if rec.session_seq != expected:
                if rec.session_seq > expected:
                    stats["session_chain_gaps"] += 1
                else:
                    stats["session_reordered"] += 1
                continue
            if len(s["events"]) < hist:
                stats["session_chain_gaps"] += 1
                continue
            window = s["events"][len(s["events"]) - hist:] + [event]
            redo = window_hash(np.stack(window)).hex()
            if redo == rec.session_hash:
                stats["session_verified"] += 1
            else:
                stats["session_hash_mismatch"] += 1
                if len(stats["session_mismatch_samples"]) < max_samples:
                    stats["session_mismatch_samples"].append({
                        "decision_id": rec.decision_id,
                        "account_id": rec.account_id,
                        "session_seq": rec.session_seq,
                        "session_len": rec.session_len,
                        "recorded": rec.session_hash,
                        "recomputed": redo,
                    })
        # Commit in row order (the append half of the batch-snapshot
        # semantics), adopting recorded seqs so a gap resyncs forward
        # instead of cascading mismatches.
        reset_done: set[str] = set()
        for a, event, seq, ts in committed:
            tw = _twin(a)
            if snap[a]["reset"] and a not in reset_done:
                tw["events"] = []
                reset_done.add(a)
            tw["events"].append(event)
            del tw["events"][:-twin_keep]
            tw["seq"] = seq
            tw["last_ts"] = ts

    chunk: list = []
    prefix = None
    for rec in records:
        if not rec.session_hash:
            continue
        p = rec.decision_id.rsplit(".", 1)[0]
        if prefix is not None and p != prefix and chunk:
            flush_chunk(chunk)
            chunk = []
        prefix = p
        chunk.append(rec)
    if chunk:
        flush_chunk(chunk)
    stats["session_ok"] = (
        stats["session_hash_mismatch"] == 0
        and stats["session_reordered"] == 0)
    return stats


def replay_directory(directory: str, *, batch: int = 256,
                     checkpoint: str | None = None,
                     vault_dir: str | None = None,
                     max_mismatch_samples: int = 10) -> dict:
    """Replay every record in a ledger directory; returns the verdict
    artifact dict (``ok`` iff zero mismatches AND zero params-fingerprint
    mismatches; index-mode records without a snapshot are counted as
    skipped, never as passes).

    Promotion side-records (serve/ledger.PromotionRecord) are read from
    the same WAL: they land in the verdict as the ``promotions``
    timeline, and the params vault they point at (default
    ``<directory>/params-vault``) resolves every fingerprint a promotion
    put into service — replay works ACROSS the promotion boundary, one
    engine per (backend, fingerprint) group."""
    from igaming_platform_tpu.serve import ledger as ledger_mod

    if vault_dir is None:
        default_vault = os.path.join(directory, "params-vault")
        vault_dir = default_vault if os.path.isdir(default_vault) else None

    records = []
    promotions = []
    for kind, rec in ledger_mod.iter_entries(directory):
        if kind == "decision":
            records.append(rec)
        elif kind == "promotion":
            promotions.append(rec)
    groups: dict[tuple, list] = {}
    skipped_no_snapshot = 0
    for r in records:
        if r.features is None:
            skipped_no_snapshot += 1
            continue
        backend = r.model_version.split("+", 1)[0]
        tier_class = "heuristic" if r.tier == "heuristic" else "compiled"
        key = (tier_class, backend, r.block_threshold, r.review_threshold,
               r.params_fp)
        groups.setdefault(key, []).append(r)

    engines = _EngineCache(batch, checkpoint, vault_dir=vault_dir)
    mismatches: list[dict] = []
    params_mismatch = 0
    replayed_by_tier: dict[str, int] = {}
    replayed_by_fp: dict[str, int] = {}
    try:
        for (tier_class, backend, block, review, fp), recs in sorted(
                groups.items()):
            if tier_class == "heuristic":
                recomputed = _replay_heuristic(recs, (block, review))
            else:
                engine = engines.get_for(backend, fp)
                if engine is None:
                    params_mismatch += len(recs)
                    continue
                engine.set_thresholds(block, review)
                replayed_by_fp[fp] = replayed_by_fp.get(fp, 0) + len(recs)
                recomputed = _replay_compiled(engine, recs)
            for rec, redo in zip(recs, recomputed):
                replayed_by_tier[rec.tier] = replayed_by_tier.get(rec.tier, 0) + 1
                was = _recorded_fields(rec)
                if was != redo and len(mismatches) < max_mismatch_samples:
                    mismatches.append({
                        "decision_id": rec.decision_id,
                        "account_id": rec.account_id,
                        "tier": rec.tier,
                        "recorded": was,
                        "recomputed": redo,
                    })
                elif was != redo:
                    mismatches.append({"decision_id": rec.decision_id})
    finally:
        engines.close()

    # Stateful decisions: reconstruct session windows from ledger event
    # order and verify every session_state_hash bit-exact — this covers
    # exactly the index-mode records the snapshot replay must skip, so
    # between the two passes every decision is either re-scored or its
    # mutable-state input proven.
    session = verify_session_chain(records)

    replayed = sum(replayed_by_tier.values())
    return {
        "metric": "decision_replay_bit_exact",
        "ledger_dir": directory,
        "records_total": len(records),
        "replayed": replayed,
        "replayed_by_tier": replayed_by_tier,
        "replayed_by_params_fp": replayed_by_fp,
        "skipped_no_snapshot": skipped_no_snapshot,
        "params_fingerprint_mismatch": params_mismatch,
        "params_vault": vault_dir,
        **session,
        "promotions": [{
            "event": p.event, "old_fp": p.old_fp, "new_fp": p.new_fp,
            "reason": p.reason, "ts": round(p.ts_unix, 3),
        } for p in promotions],
        "fields_compared": list(_COMPARE_FIELDS),
        "mismatches": len(mismatches),
        "mismatch_samples": mismatches[:max_mismatch_samples],
        "ok": (not mismatches and params_mismatch == 0
               and (replayed > 0 or session["session_verified"] > 0)
               and session["session_ok"]),
    }


# ---------------------------------------------------------------------------
# --verify: the self-contained smoke (make replay-verify)


def run_verify(ledger_dir: str | None = None, *, rows: int = 96,
               batch: int = 64, chaos_plan: str | None = None) -> dict:
    """Score a seeded batch — device path, batcher path, and a forced
    degraded (heuristic) window — under a chaos plan with ledger-append
    faults, then replay the ledger and diff bit-exact."""
    import tempfile

    from igaming_platform_tpu.core.config import BatcherConfig, ScoringConfig
    from igaming_platform_tpu.serve import chaos as chaos_mod
    from igaming_platform_tpu.serve import ledger as ledger_mod
    from igaming_platform_tpu.serve.scorer import ScoreRequest, TPUScoringEngine
    from igaming_platform_tpu.serve.supervisor import (
        ServingSupervisor,
        SupervisedScoringEngine,
    )

    directory = ledger_dir or tempfile.mkdtemp(prefix="ledger-verify-")
    plan_str = chaos_plan or os.environ.get(
        "CHAOS_PLAN", "seed=5;ledger.append=delay:p=0.4:ms=1")
    plan = chaos_mod.install(plan_str)

    sup = ServingSupervisor(failure_threshold=2, open_s=0.5)

    def factory():
        return TPUScoringEngine(
            ScoringConfig(), ml_backend="mock",
            batcher_config=BatcherConfig(batch_size=batch, max_wait_ms=1.0))

    engine = SupervisedScoringEngine(factory, supervisor=sup)
    ledger = ledger_mod.DecisionLedger(
        directory, breaker=sup.breaker("ledger"))
    engine.inner.ledger = ledger
    ledger_mod.set_state_provider(lambda: sup.state)
    try:
        from igaming_platform_tpu.serve.feature_store import TransactionEvent

        for i in range(64):
            engine.update_features(TransactionEvent(
                account_id=f"rv-{i % 32}", amount=500 + 37 * i,
                tx_type=("deposit", "bet", "withdraw")[i % 3],
                ip=f"10.9.{i % 20}.{i % 25}", device_id=f"dev-{i % 8}"))
        reqs = [ScoreRequest(f"rv-{i % 32}", amount=900 + 131 * i,
                             tx_type=("deposit", "bet", "withdraw")[i % 3])
                for i in range(rows)]
        # Device path (direct batch) + the batcher path.
        engine.score_batch(reqs)
        for i in range(8):
            engine.score(reqs[i])
        # Forced degraded window: the heuristic tier's decisions must be
        # ledgered and replayable too.
        sup.breaker("device").force_open("replay-verify degraded window")
        engine.score_batch(reqs[:rows // 2])
        sup.breaker("device").reset()
    finally:
        ledger.close()
        chaos_mod.clear()
        ledger_mod.set_state_provider(None)
        engine.close()

    verdict = replay_directory(directory, batch=batch)
    verdict["scenario"] = "replay-verify smoke"
    verdict["chaos_plan"] = plan.snapshot()
    verdict["ledger_stats_note"] = (
        "append-fault drops are counted by the ledger, not replayed — "
        "replay covers every record that reached the WAL")
    verdict["degraded_records_replayed"] = verdict["replayed_by_tier"].get(
        "heuristic", 0)
    verdict["ok"] = bool(
        verdict["ok"] and verdict["degraded_records_replayed"] > 0)
    return verdict


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="Re-score a decision ledger bit-exact")
    parser.add_argument("--dir", help="ledger directory (WAL segments)")
    parser.add_argument("--out", help="write the verdict artifact here")
    parser.add_argument("--batch", type=int, default=256,
                        help="replay engine batch size")
    parser.add_argument("--checkpoint",
                        help="pinned Orbax checkpoint (default: the seeded "
                             "init convention)")
    parser.add_argument("--params-vault",
                        help="fingerprint-keyed params vault for replay "
                             "across promotion boundaries (default: "
                             "<dir>/params-vault when present)")
    parser.add_argument("--verify", action="store_true",
                        help="self-contained smoke: score under CHAOS_PLAN, "
                             "replay, diff")
    args = parser.parse_args(argv)

    if args.verify:
        verdict = run_verify()
    elif args.dir:
        verdict = replay_directory(args.dir, batch=args.batch,
                                   checkpoint=args.checkpoint,
                                   vault_dir=args.params_vault)
    else:
        parser.error("need --dir or --verify")
    print(json.dumps(verdict))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(verdict, f, indent=1)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
