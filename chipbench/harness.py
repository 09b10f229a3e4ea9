"""One run of one cell: boot the risk.v1 server in-process, fill its
resident state, check its outputs against the plain reference, warm the
cell's shapes, measure a window, reduce what it left behind.

The program is driven through its normal entry points only
(``serve/server.RiskServer``, a real gRPC socket, ``ScoreBatch``); the
harness edits nothing in it. Two seams are used from outside:
``serve/ledger.wall_clock`` (the program's injected clock seam) is held
to a seeded time during the output check, so that inter-event gaps come
from the seed; and where the session head has parameters they are
replaced, before any traffic, by the seeded tree that the head's file
under ``chipbench/heads/`` makes. Where the configuration preloads
session events, they go in as a restart brings them back: appended to
the host index (``session_state.group_chunk`` and ``prepare_chunk``, the
calls scoring makes), then put into the ring by the admission hook.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time

import numpy as np

from chipbench import reference, traffic, trace_reduce, validate
from chipbench.readers import Readings, read_all

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "out")
FILL_NOW = 1_800_000_000.0  # the fixed "now" of every admission gather
SCORE_BATCH = "/risk.v1.RiskService/ScoreBatch"
# The rehearsal (CPU, --rehearse) runs every phase at this size.
REHEARSAL = {"resident_accounts": 4096, "store_loaded_accounts": 1024,
             "fill_chunk": 1024, "pool_frames": 64, "warm_up_s": 0.5}
TRACE_SLICE_S = 2.5
# Histories are drawn and handed to the host index this many draws at a
# time (16 MB of float64): arrays of that size come from the allocator's
# heap again and again, where larger ones are mapped, and faulted in, anew.
PRELOAD_DRAWS = 1 << 21
PRELOAD_PROBES = 64  # accounts whose window is read back after the fill
WARM_UP_S = 1.0  # of the pool's own traffic from every client, before t0
RPC_TIMEOUT_S = 120.0


def process_age_s() -> float:
    """Seconds since this process was created (so set-up includes the
    interpreter's start and every import)."""
    with open("/proc/self/stat", encoding="ascii") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime", encoding="ascii") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


COMPARED: list[str] = []  # every number compared, beside its limit


def log(msg: str) -> None:
    if msg.startswith("check "):
        COMPARED.append(msg)
    print(f"[chipbench {process_age_s():7.2f}s] {msg}", flush=True)


class Clock:
    """Stands in for ``serve/ledger.wall_clock`` while the check runs."""

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def head_operand_dtype(head, platform: str, stated: str) -> str:
    """The operand dtype the session head's reference is read at. XLA's CPU
    backend multiplies float32 operands as they are and only the MXU rounds
    them, so a head that leaves the rounding to the default precision (the
    transformer head's ``x @ w``) is float32 in a rehearsal; a head whose
    program casts its operands itself (``CASTS_OPERANDS`` in its
    reference's file) is at the stated dtype on any backend. The trunk
    casts explicitly on both."""
    if platform == "cpu" and not getattr(head, "CASTS_OPERANDS", False):
        return "float32"
    return stated


def account_major_groups(session_state, ids: list[str], counts, verify=False):
    """What ``session_state.group_chunk`` makes of a chunk whose rows lie
    account after account (``counts[u]`` rows of ``ids[u]``), written down
    without its pass over every row in Python: a history's rows are tens
    of millions and their grouping is known. ``verify`` holds the result
    to ``group_chunk`` itself, field for field."""
    counts = np.asarray(counts, np.int64)
    nu, b = len(ids), int(counts.sum())
    if nu == b:
        rows = np.arange(b)
        groups = session_state.ChunkGroups(
            ids, rows, np.zeros((b,), np.int32), [1] * b, range(b), rows,
            range(b + 1))
    else:
        bounds = np.concatenate([[0], np.cumsum(counts)])
        uidx = np.repeat(np.arange(nu), counts)
        occ = (np.arange(b) - bounds[:-1][uidx]).astype(np.int32)
        groups = session_state.ChunkGroups(
            ids, uidx, occ, counts.tolist(), bounds[:-1].tolist(),
            np.arange(b), bounds.tolist())
    if verify:
        theirs = session_state.group_chunk(
            np.array(ids, dtype=object).repeat(counts).tolist())
        for name in groups.__slots__:
            mine, want = getattr(groups, name), getattr(theirs, name)
            if not (type(mine) is type(want) and list(mine) == list(want)
                    and getattr(mine, "dtype", None) == getattr(want, "dtype", None)):
                raise SystemExit(f"preload: group_chunk's {name} is no longer "
                                 "what the harness writes for account-major rows")
    return groups


class Run:
    def __init__(self, spec: dict, *, seed: int, seconds: float, trace: bool,
                 rehearse: bool):
        self.spec, self.seed, self.seconds = spec, int(seed), float(seconds)
        self.trace, self.rehearse = trace, rehearse
        self.config = dict(spec["config"])
        self.mix = dict(spec["traffic"])
        if rehearse:
            for key in ("resident_accounts", "store_loaded_accounts",
                        "fill_chunk"):
                self.config[key] = REHEARSAL[key]
            self.config["env"] = dict(
                self.config["env"],
                FEATURE_CACHE_CAPACITY=str(REHEARSAL["resident_accounts"]))
            self.mix["pool_frames"] = REHEARSAL["pool_frames"]
        self.phase_s: dict[str, float] = {}
        self.pool = None
        self.history = None  # account id -> its preloaded history
        self.index_mode = self.mix["rpc"] == "index"

    # -- boot ---------------------------------------------------------------

    def boot(self) -> None:
        t0 = time.perf_counter()
        os.environ.update(self.config["env"])
        import jax

        from igaming_platform_tpu.core import devices as devices_mod

        backend = devices_mod.require_device()
        if backend == "cpu" and not self.rehearse:
            raise SystemExit("no accelerator: this benchmark measures a TPU; "
                             "pass --rehearse for a CPU rehearsal")
        chips = int(self.spec["cell"]["chips"])
        if backend != "cpu" and len(jax.devices()) < chips:
            raise SystemExit(f"the cell needs {chips} chip(s); JAX found "
                             f"{len(jax.devices())}")
        self.jax, self.device = jax, jax.devices()[0]
        devices_mod.enable_persistent_compile_cache()

        import dataclasses

        import grpc

        from igaming_platform_tpu.core.config import RiskServiceConfig
        from igaming_platform_tpu.serve.server import RiskServer, device_gate

        device_gate()
        self.params = reference.make_params(self.seed, tuple(self.config["trunk"]))
        config = RiskServiceConfig.from_env()
        config = dataclasses.replace(config, batcher=dataclasses.replace(
            config.batcher, batch_size=int(self.config["env"]["BATCH_SIZE"])))
        self.server = RiskServer(config, ml_backend=self.config["ml_backend"],
                                 params=self.params, grpc_port=0, http_port=0)
        inner = self.inner = self.server.engine.inner
        if inner.cache is None or inner.session is None:
            raise SystemExit("the server booted without its feature cache or "
                             "session plane")
        if backend != "cpu" and type(inner.features).__name__ != "NativeFeatureStore":
            raise SystemExit("a TPU boot must serve from the native feature store")
        # the session head's reference is a file of its own, found by the
        # name the configuration gives; a head with parameters has them
        # replaced by that file's seeded tree
        self.head = validate.load_code(
            "heads", validate.head_name(self.config), self.spec["root"])
        self.head_params = self.head.make_params(self.seed, self.config)
        if self.head_params is not None:
            inner.session.head_params = jax.device_put(self.head_params, self.device)
        self.channel = grpc.insecure_channel(
            f"localhost:{self.server.grpc_port}",
            options=[("grpc.max_receive_message_length", 64 << 20),
                     ("grpc.max_send_message_length", 64 << 20)])
        self.call = self.channel.unary_unary(
            SCORE_BATCH, request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        self.phase_s["boot"] = time.perf_counter() - t0
        log(f"boot {self.phase_s['boot']:.2f} s on {self.device.device_kind} "
            f"(head={inner.session.head}, reference "
            f"heads/{validate.head_name(self.config)}.py)")

    def shutdown(self) -> None:
        self.channel.close()
        self.server.shutdown(grace=5.0)
        gc.unfreeze()  # what window() froze; a test process lives on

    # -- resident state ------------------------------------------------------

    def fill(self) -> None:
        """Admit every resident account through the cache's own lookup, in
        equal chunks, so one CLOCK governs table and ring as in serving."""
        t0 = time.perf_counter()
        cfg, inner = self.config, self.inner
        resident = int(cfg["resident_accounts"])
        self.pop = pop = traffic.Population(self.mix, resident, self.seed)
        rng = traffic.rng_for(self.seed, "store")
        loaded = min(int(cfg["store_loaded_accounts"]), resident)
        agg = {
            "total_deposits": rng.integers(0, 500_000, loaded),
            "total_withdrawals": rng.integers(0, 300_000, loaded),
            "deposit_count": rng.integers(0, 60, loaded),
            "withdraw_count": rng.integers(0, 30, loaded),
            "total_bets": rng.integers(0, 900_000, loaded),
            "total_wins": rng.integers(0, 800_000, loaded),
            "bet_count": rng.integers(0, 400, loaded),
            "win_count": rng.integers(0, 200, loaded),
            "bonus_claim_count": rng.integers(0, 8, loaded),
        }
        age_days = rng.uniform(0.5, 900.0, loaded)
        cols = {k: v.tolist() for k, v in agg.items()}
        created = (FILL_NOW - age_days * 86_400.0).tolist()
        load = inner.features.load_batch_features
        for r in range(loaded):
            load(pop.id_of_rank(r), created_at=created[r],
                 **{k: v[r] for k, v in cols.items()})
        self.phase_s["store"] = time.perf_counter() - t0
        spec = traffic.history_spec(cfg["session_events_preloaded"])
        preloaded = 0 if spec is None else self.preload(spec)
        t1 = time.perf_counter()
        chunk = int(cfg["fill_chunk"])
        hook = inner.cache.session_hook
        if spec is None:
            # A never-seen account's window is all zeros, which is what a
            # freshly booted ring already holds in every slot, so the fill
            # admits with the hook detached and leaves the same state the
            # sync would, at no launch: every resident account's window is
            # EMPTY when the window starts (`session_events_preloaded` 0,
            # under `reduced`). The eight cells the benchmark had before
            # PR 56 are measured from that state.
            inner.cache.session_hook = None
        try:
            for lo in range(0, resident, chunk):
                inner.cache.lookup(pop.ids[lo:lo + chunk], now=FILL_NOW)
        finally:
            inner.cache.session_hook = hook
        self.jax.block_until_ready((inner.cache.table, inner.session.session_ring))
        stats = inner.cache.stats()
        if stats["occupancy"] != resident or stats["evictions"]:
            raise SystemExit(f"fill left the cache at {stats}")
        if spec is not None:
            self.probe_windows(spec)
        self.phase_s["fill"] = time.perf_counter() - t1
        log(f"fill: population+store {self.phase_s['store']:.2f} s, "
            f"{resident} accounts admitted in {self.phase_s['fill']:.2f} s, "
            f"{preloaded} session events preloaded")
        self.memory_line("after fill")

    def preload(self, spec: dict) -> int:
        """Every resident account's history into the host index, as
        scoring appends events to it: ``rounds`` chunks an account, round
        ``j`` carrying the ``j``-th share of its events on that round's
        clock, so the gaps between rounds are real and seeded and the
        events of one round share an arrival, as the rows of one frame do.
        Host only; the admission that follows puts each window into the
        ring (``on_admit``), as after a restart. One block of accounts is
        held at a time beside the index."""
        from igaming_platform_tpu.serve import session_state

        t0 = time.perf_counter()
        session, pop = self.inner.session, self.pop
        resident = pop.n
        clocks = traffic.history_clocks(self.seed, spec["rounds"])
        ids = np.array(pop.ids, dtype=object)[pop.perm]  # by rank
        block = max(1, PRELOAD_DRAWS // (1 + 3 * spec["high"]))
        events = 0
        for lo in range(0, resident, block):
            h = traffic.histories(self.mix, self.seed, lo,
                                  min(block, resident - lo), spec)
            amounts = h["amounts"].astype(np.float32)  # as the wire decoder
            types = h["types"].astype(np.int32)        # hands them on
            for j in np.unique(h["round"]):
                rows = np.flatnonzero(h["round"] == j)
                present, counts = np.unique(h["account"][rows],
                                            return_counts=True)
                groups = account_major_groups(
                    session_state, ids[lo + present].tolist(), counts,
                    verify=events == 0)
                with session.lock:
                    session.prepare_chunk(groups, amounts[rows], types[rows],
                                          float(clocks[j]))
            events += len(amounts)
        self.phase_s["preload"] = time.perf_counter() - t0
        log(f"preload: {events} events of {resident} accounts "
            f"({spec['low']}-{spec['high']} each, {spec['rounds']} rounds "
            f"ending {clocks[-1] - clocks[0]:.0f} s apart) into the host "
            f"index in {self.phase_s['preload']:.2f} s")
        return events

    def probe_windows(self, spec: dict) -> None:
        """The guarantee the preload rests on, read back: for a seeded
        sample of accounts the host index's window and the ring's rows of
        the account's slot are, bit for bit, the last ``SESSION_EVENTS``
        events of its history as the reference encodes them."""
        from igaming_platform_tpu.serve import session_state

        session, pop = self.inner.session, self.pop
        n_ev = session.n_events
        ranks = traffic.rng_for(self.seed, "probe").choice(
            pop.n, size=min(PRELOAD_PROBES, pop.n), replace=False)
        ids = [pop.id_of_rank(r) for r in ranks]
        wants = [reference.encode_history(
            traffic.history_of(self.mix, self.seed, r, spec))[0][-n_ev:]
            for r in ranks]
        ring = None
        if session.plan is None:  # a sharded ring is not gathered whole
            slots = self.inner.cache.lookup(ids, now=FILL_NOW)
            ring = np.asarray(session_state.ring_rows(
                session.session_ring, self.jax.numpy.asarray(slots), n_ev))
        for i, (account, want) in enumerate(zip(ids, wants)):
            twin = session.twin_window(account)
            if twin.shape != want.shape or twin.tobytes() != want.tobytes():
                raise SystemExit(
                    f"preload: the host index holds {twin.shape[0]} events of "
                    f"{account} that are not its history's last {len(want)}")
            if ring is not None and (
                    ring[i, :len(want)].tobytes() != want.tobytes()
                    or ring[i, len(want):].any()):
                raise SystemExit(f"preload: the ring's rows of {account} are "
                                 f"not its history's last {len(want)} events")
        log(f"preload: {len(ids)} probed accounts hold their history's last "
            f"events in the host index"
            + ("" if ring is None else " and in the ring")
            + f", {sum(len(w) == n_ev for w in wants)} of them a full window")

    def memory_line(self, when: str) -> dict:
        # the fullest of the chips the cell runs on
        chips = self.jax.devices()[:int(self.spec["cell"]["chips"])]
        stats = max((d.memory_stats() or {} for d in chips),
                    key=lambda s: s.get("peak_bytes_in_use") or 0)
        log(f"memory {when}: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
        return stats

    # -- the output check ----------------------------------------------------

    def check(self) -> tuple[bool, dict]:
        """Send the seeded check sequence once and hold every reply to the
        plain reference at the configuration's stated precision."""
        from igaming_platform_tpu.serve import ledger as ledger_mod

        t0 = time.perf_counter()
        cfg, inner = self.config, self.inner
        seq = traffic.check_sequence(
            self.mix, self.pop, self.seed,
            loaded=int(cfg["store_loaded_accounts"]),
            stored=int(cfg["store_accounts"]))
        spec = traffic.history_spec(cfg["session_events_preloaded"])
        if spec is not None:
            # the reference starts each account it meets from the history
            # the fill gave it: the first check RPC scores warm windows
            ranks = {a: self.pop.rank_of_id(a) for rpc in seq for a in rpc["ids"]}
            mix, seed = self.mix, self.seed
            self.history = lambda a: traffic.history_of(mix, seed, ranks[a], spec)
        clock, real_clock = Clock(), ledger_mod.wall_clock
        self.check_log = []
        ledger_mod.wall_clock = clock
        try:
            for rpc in seq:
                n = len(rpc["ids"])
                clock.now = rpc["clock"]
                gather_now = FILL_NOW if self.index_mode else time.time()
                base = self.base_rows(rpc["ids"], gather_now)
                reply = self.call(traffic.encode_frame(
                    self.mix["rpc"], rpc["ids"], rpc["amounts"], rpc["types"]),
                    timeout=300)
                got = traffic.decode_proto_response(reply)
                if len(got["score"]) != n:
                    raise SystemExit(f"check RPC returned {len(got['score'])} "
                                     f"of {n} rows")
                self.check_log.append((rpc, base, got))
        finally:
            ledger_mod.wall_clock = real_clock
        verdict = self.judge(cfg["precision"]["reference_operand_dtype"])
        self.phase_s["check"] = time.perf_counter() - t0
        return verdict

    def base_rows(self, ids: list[str], now: float) -> np.ndarray:
        """The feature store's host gather of the accounts' base rows (no
        transaction context), as the cache takes them at admission."""
        store, n = self.inner.features, len(ids)
        if hasattr(store, "gather_columns"):
            return store.gather_columns(ids, [0] * n, [""] * n, now=now)[0]
        x = np.zeros((n, reference.N_FEATURES), np.float32)
        for i, a in enumerate(ids):
            store.fill_row(x[i], a, 0, "", now=now)
        return x

    def judge(self, operand_dtype: str, control: bool = False) -> tuple[bool, dict]:
        """The recorded replies against the reference at the configuration's
        stated precision. With ``control`` the replies are set aside and
        the reference computed with operands rounded to ``operand_dtype``,
        one precision step down, stands in the program's place: it has to
        come out as not correct."""
        cfg = self.config
        stated = cfg["precision"]["reference_operand_dtype"]
        head_stated = head_operand_dtype(self.head, self.device.platform, stated)

        step = int(cfg["env"]["BATCH_SIZE"])

        def replay(dtype, head_dtype):
            ref = reference.Reference(
                self.params, head=self.head, head_params=self.head_params,
                n_events=int(cfg["env"]["SESSION_EVENTS"]),
                operand_dtype=dtype, head_operand_dtype=head_dtype,
                history=self.history)
            for rpc, base, _ in self.check_log:
                if self.index_mode:
                    # the server scores a frame in chunks of BATCH_SIZE
                    # rows, one after the other on one clock: an account
                    # that repeats across chunks sees its earlier event
                    yield reference.concat([
                        ref.score_index(rpc["ids"][lo:lo + step],
                                        base[lo:lo + step],
                                        rpc["amounts"][lo:lo + step],
                                        rpc["types"][lo:lo + step], rpc["clock"])
                        for lo in range(0, len(rpc["ids"]), step)])
                else:
                    yield ref.score_rows(base, rpc["amounts"], rpc["types"])

        wants = list(replay(stated, head_stated))
        if self.index_mode and not control:
            first, n_ev = wants[0]["lengths"], int(cfg["env"]["SESSION_EVENTS"])
            log(f"windows at the first check RPC: {int((first == n_ev).sum())} "
                f"of {len(first)} rows full, mean length {first.mean():.1f} "
                f"of {n_ev}")
        exacts = list(replay("float32", "float32"))
        if control:
            gots = [reference.as_reply(o)
                    for o in replay(operand_dtype, operand_dtype)]
        else:
            gots = [got for _, _, got in self.check_log]
        numbers = reference.merge([reference.compare(g, w, e)
                                   for g, w, e in zip(gots, wants, exacts)])
        ok, lines = reference.judge(numbers, cfg["limits"])
        tag = "control" if control else "check"
        for line in lines:
            log(line.replace("check", tag, 1))
        log(f"{tag} rows={numbers['rows']} warm={numbers['warm_rows']} "
            f"folded={numbers['folded_rows']} operands={operand_dtype}")
        return ok, numbers

    # -- counters ------------------------------------------------------------

    def counters(self) -> dict:
        """Every counter the per-layer readers may name: the program's
        metric registry by Prometheus name (labels summed), the cache's and
        session plane's own counters, the compile count."""
        out: dict[str, float] = {}
        for line in self.server.metrics.registry.render_text().splitlines():
            if line.startswith("# TYPE ") and line.endswith(" counter"):
                # a counter nothing has incremented renders no sample: it
                # reads 0, not "nothing to read"
                out.setdefault(line.split(" ")[2], 0.0)
            if line.startswith("#") or " " not in line:
                continue
            name, value = line.rsplit(" ", 1)
            name = name.split("{", 1)[0]
            try:
                out[name] = out.get(name, 0.0) + float(value)
            except ValueError:
                continue
        for k, v in self.inner.cache.stats().items():
            out[f"cache.{k}"] = float(v)
        snap = self.inner.session.snapshot()
        out["session.appends"] = float(snap["appends"])
        for k, v in snap["rows"].items():
            out[f"session.rows_{k}"] = float(v)
        out["compiles"] = float(
            self.server.telemetry.compile_watcher.compiles_total)
        return out

    def stage_totals(self) -> dict:
        from igaming_platform_tpu.obs import hostprof

        stages = hostprof.get_default().snapshot()["stages"]
        return {name: float(s["total_us"]) for name, s in stages.items()}

    # -- the window ----------------------------------------------------------

    def drive(self, pool, cursor: list[int], t_end: float) -> tuple[list, list]:
        """Starts the closed loop until ``t_end``: client ``c`` of ``k``
        sends frames ``c, c+k, ...`` of the pool from where ``cursor[c]``
        says it stopped, each when its previous reply has arrived. Returns
        the list that holds ``(due, done, rows, ok)`` of every RPC once the
        threads, returned beside it, have been joined."""
        import grpc

        records: list[tuple] = []
        lock = threading.Lock()
        k = len(cursor)

        def closed_client(c: int) -> None:
            mine, i = pool[c::k], cursor[c]
            local, due = [], time.perf_counter()
            while due < t_end:
                payload, rows = mine[i % len(mine)]
                i += 1
                try:
                    self.call(payload, timeout=RPC_TIMEOUT_S)
                    ok = True
                except grpc.RpcError:
                    ok = False
                done = time.perf_counter()
                local.append((due, done, rows, ok))
                due = done
            cursor[c] = i
            with lock:
                records.extend(local)

        threads = [threading.Thread(target=closed_client, args=(c,),
                                    name=f"chipbench-client-{c}")
                   for c in range(k)]
        for t in threads:
            t.start()
        return records, threads

    def warm_up(self, pool, cursor: list[int]) -> None:
        """The pool's own traffic from every client for ``WARM_UP_S``
        seconds: every frame size, every client thread's path and the
        process's first second of serving, unlike the rest in every run
        (PERF.md, PR 28), are behind it when the window starts. The
        window goes on where this stops in the pool, so it scores the same
        kind of accounts (mostly never seen) as it would without."""
        t0 = time.perf_counter()
        records, threads = self.drive(
            pool, cursor,
            t0 + (REHEARSAL["warm_up_s"] if self.rehearse else WARM_UP_S))
        for t in threads:
            t.join()
        failed = sum(1 for r in records if not r[3])
        if failed:
            raise SystemExit(f"{failed} of {len(records)} warm-up RPCs failed")
        self.phase_s["warm_up"] = time.perf_counter() - t0

    def window(self) -> dict:
        if self.pool is None:
            self.pool = traffic.build_pool(self.mix, self.pop, self.seed)
            # the population (a list of every account id) has done its work:
            # a real client holds no such list, and the collector would
            # walk it
            self.pop = None
            # and everything that lives on (the server's structures, the
            # pool) leaves the collector's generations: a full collection
            # in the window then walks what the window made, ~30 ms where
            # it was ~140 (PERF.md, PR 28)
            gc.collect()
            gc.freeze()
        pool = self.pool
        cursor = [0] * int(self.mix["clients"])
        self.warm_up(pool, cursor)
        c0, s0 = self.counters(), self.stage_totals()
        tracer = Tracer(self) if self.trace else None
        self.setup_s = process_age_s()
        t0 = time.perf_counter()
        records, threads = self.drive(pool, cursor, t0 + self.seconds)
        if tracer:
            tracer.slice(t0, self.seconds)
        for t in threads:
            t.join()
        if tracer:
            tracer.load()
        t_last = max(r[1] for r in records)
        c1, s1 = self.counters(), self.stage_totals()
        log(f"window: {len(records)} RPCs in {t_last - t0:.3f} s after "
            f"{self.phase_s['warm_up']:.2f} s of warm-up")
        return self.reduce(records, t0, t_last, c0, c1, s0, s1, tracer)

    # -- reduction -----------------------------------------------------------

    def reduce(self, records, t0, t_last, c0, c1, s0, s1, tracer) -> dict:
        due, done, rows, ok = np.array(records, float).T
        ok = ok.astype(bool)
        elapsed = t_last - t0
        rows_ok = int(rows[ok].sum())
        # a shed or failed RPC counts as slower than any reply
        latency_ms = np.where(ok, (done - due) * 1000.0, np.inf)
        chunk_rows = int(self.config["env"]["BATCH_SIZE"])
        pads: dict[int, int] = {}  # padded batch -> executions
        for size, count in zip(*np.unique(rows[ok], return_counts=True)):
            # a frame runs as whole chunks of BATCH_SIZE rows and its rest
            for part, times in ((chunk_rows, int(size) // chunk_rows),
                                (int(size) % chunk_rows, 1)):
                if part and times:
                    shape = int(self.inner._pick_shape(part))
                    pads[shape] = pads.get(shape, 0) + times * int(count)
        chunks_ok = int(np.ceil(rows[ok] / chunk_rows).sum())
        counters = {k: c1[k] - c0.get(k, 0.0) for k in c1}
        counters.update({"client.rpcs_sent": float(len(rows)),
                         "client.rpcs_ok": float(ok.sum()),
                         "client.rows_ok": float(rows_ok),
                         "client.chunks_ok": float(chunks_ok)})
        # the median reply time of all RPCs of the window, failed ones
        # counted as slower than any reply
        p50 = float(np.percentile(latency_ms, 50))
        if not np.isfinite(p50):
            p50 = RPC_TIMEOUT_S * 1e3
        end_to_end = {"txns_per_s": rows_ok / elapsed, "rpc_p50_ms": p50,
                      "setup_s": self.setup_s}
        # in-window identities: they are part of `correct`
        scored = counters["session.appends"] + counters["session.rows_bypass"]
        failed_chunks = int(np.ceil(rows[~ok] / chunk_rows).sum())
        dispatches = counters["risk_device_dispatches_total"]
        identities = {
            "rows_acked_minus_rows_scored": (rows_ok - scored, 0, 0),
            "dispatches_minus_chunks": (dispatches - chunks_ok, 0, failed_chunks),
            "compiles_in_window": (counters["compiles"], 0, 0),
        }
        correct = True
        for key, (value, lo, hi) in identities.items():
            good = lo <= value <= hi
            correct = correct and good
            log(f"check {key} = {value!r} limit [{lo}, {hi}] "
                f"{'ok' if good else 'FAILED'}")
        readings = Readings(
            config=self.config, rows_ok=rows_ok,
            stages={k: s1[k] - s0.get(k, 0.0) for k in s1},
            counters=counters,
            latency_ms=latency_ms,
            index_mode=self.index_mode, device_kind=self.device.device_kind,
            pad_rows=pads, root=self.spec["root"])
        breakdown = None
        device_extra = {}
        if tracer is not None and tracer.result is not None:
            readings.trace, readings.trace_window, spans = tracer.result
            busy = trace_reduce.busy_seconds(readings.trace, readings.trace_window)
            lo, hi = readings.trace_window
            device_extra = {"busy_s": busy, "window_s": (hi - lo) / 1e9}
            breakdown = {
                "device_ops": trace_reduce.top_device_ops(
                    readings.trace, readings.trace_window),
                "idle_gaps": trace_reduce.idle_gaps(
                    readings.trace, readings.trace_window, spans),
            }
        per_layer = read_all(self.spec["per_layer"], readings, log)
        finite = latency_ms[np.isfinite(latency_ms)]
        log("latency ms: " + json.dumps({
            f"p{q}": round(float(np.percentile(finite, q)), 3)
            for q in (50, 75, 90, 95, 99, 100)}))
        from igaming_platform_tpu.obs import hostprof
        log("gc: " + json.dumps(hostprof.get_default().gc_snapshot())[:600])
        log("stages us/row: " + json.dumps(
            {k: round(v / max(rows_ok, 1), 3)
             for k, v in sorted(readings.stages.items()) if v}))
        return {
            "correct": correct, "attempted": int(len(rows)),
            "failed": int((~ok).sum()), "elapsed_s": elapsed,
            "end_to_end": {
                m["name"]: {"value": float(end_to_end[m["name"]]),
                            "unit": m["unit"]}
                for m in self.spec["end_to_end"]},
            "per_layer": per_layer, "breakdown": breakdown,
            "device_extra": device_extra,
        }


class Tracer:
    """Profiles a short slice of the window and collects the program's
    own spans over it, both on one clock."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = os.path.join(OUT_DIR, "trace", run.spec["cell"]["name"])
        self.spans: list[tuple] = []
        self.result = None

    def _sink(self, span) -> None:
        if span.mono_end:
            self.spans.append((span.name, span.mono_start, span.mono_end))

    def slice(self, t0: float, seconds: float) -> None:
        """Called on the main thread while the clients run."""
        import shutil

        from igaming_platform_tpu.obs import tracing

        jax = self.run.jax
        length = min(TRACE_SLICE_S, seconds / 2)
        start_at = t0 + min(2.0, seconds / 4)
        shutil.rmtree(self.dir, ignore_errors=True)
        time.sleep(max(0.0, start_at - time.perf_counter()))
        jax.profiler.start_trace(self.dir)
        tracing.add_span_sink(self._sink)
        mark_lo = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench_mark_lo"):
            time.sleep(0.001)
        time.sleep(length)
        mark_hi = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench_mark_hi"):
            time.sleep(0.001)
        tracing.remove_span_sink(self._sink)
        jax.profiler.stop_trace()
        self.marks = (mark_lo, mark_hi)

    def load(self) -> None:
        """After the window: read the trace and put the spans on its clock."""
        try:
            path = trace_reduce.find_xplane(self.dir)
        except FileNotFoundError as exc:
            log(f"trace: {exc}")
            return
        trace, summary = trace_reduce.load_xplane(path)
        os.makedirs(OUT_DIR, exist_ok=True)
        trace_reduce.dump_json(trace, os.path.join(OUT_DIR, "trace_cut.json"),
                               limit=400)
        with open(os.path.join(OUT_DIR, "trace_summary.json"), "w",
                  encoding="utf-8") as f:
            json.dump(summary, f, indent=1)
        marks = {name: start for name, start, _ in trace.host_marks}
        if "chipbench_mark_lo" not in marks or "chipbench_mark_hi" not in marks:
            log(f"trace: marks not found among {sorted(marks)}")
            return
        lo, hi = marks["chipbench_mark_lo"], marks["chipbench_mark_hi"]
        offset_ns = lo - int(self.marks[0] * 1e9)  # trace clock - perf_counter
        spans = [(name, int(a * 1e9) + offset_ns, int((b - a) * 1e9))
                 for name, a, b in self.spans]
        self.result = (trace, (lo, hi), spans)
        log(f"trace: {sum(len(v) for v in trace.device_ops.values())} device "
            f"ops, {len(spans)} program spans over {(hi - lo) / 1e9:.3f} s")
