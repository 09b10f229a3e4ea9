"""AOT compiles of the session ring's programs for a DESCRIBED TPU v5e.

The sandbox has no chip, but the TPU compiler is installed: these tests
compile the fused session step and the admission sync at a deployment's
size for a v5e that is described, not attached, and hold the compiled
module to what the ring's flat at-rest layout promises
(serve/session_state.py "the ring's at-rest layout"): the donated ring
is written in place — no instruction whose result is ring-sized but the
ring itself (``_ring_sized``: no ``copy``, no ``convert``, no fusion over
it), no ring-sized temporary, outputs aliased onto the arguments. Nothing
runs, so nothing here is a time; the chip numbers are in PERF.md.

The topology is described inside a fixture of this file (never at
import): only one process may load libtpu, and under xdist only the
worker that is handed this file does.
"""

import os
import re

import numpy as np
import pytest

CAPACITY = 6_291_456          # ISSUE 24's size: 6 x 2^20 resident accounts
SHARDED_CAPACITY = 20_971_520  # PERF.md Open question 1: 5,242,880 a shard
BATCH = 256
SYNC_SLOTS = 4096
TEMP_LIMIT = 64 * 2**20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """models/sequence picks its attention core from
    ``jax.default_backend()`` while tracing: steer it to the core the
    chip runs (the Pallas flash kernel), here in the test."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _step_args(head_params, capacity, ring_rows, shards, state, repl,
               batch=BATCH):
    """Abstract arguments of the fused step (sketch variant) at the ladder
    rung of ``batch`` rows, the ring state under ``state`` and everything
    else under ``repl``; ``head_params`` is the head's init, traced for
    its shapes and never run (the ``keye`` tree is 5 GB)."""
    import jax

    from igaming_platform_tpu.core.features import NUM_FEATURES
    from igaming_platform_tpu.models.multitask import init_multitask
    from igaming_platform_tpu.serve import index_program
    from igaming_platform_tpu.serve import session_state as ss

    def abstract(make):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, repl),
                            jax.eval_shape(make))

    params = {"multitask": abstract(lambda: init_multitask(jax.random.key(0)))}
    sparams = None if head_params is None else abstract(head_params)
    n = ss.default_events()
    f32, i32 = np.float32, np.int32
    b = batch
    return (
        params, sparams,
        _spec((capacity, NUM_FEATURES), f32, state),      # table
        _spec((capacity,), np.bool_, state),              # flags
        _spec((ss.ring_size(ring_rows, n, shards),), f32, state),  # ring
        _spec((ring_rows,), i32, state),                  # cursor
        _spec((ring_rows,), i32, state),                  # length
        _spec((b, index_program.CHUNK_WORDS), i32, repl),  # the packed chunk
        _spec((2,), i32, repl), None,
    )


def _lower_step(head, capacity, ring_rows, state, repl, *, mesh=None,
                plan=None, sketch=True, batch=BATCH):
    """The fused session step the server runs, from the builder the
    server builds it with (serve/index_program.build; ``jit(_body)``, ring
    donated; the drift-sketch variant unless told otherwise), lowered."""
    from igaming_platform_tpu.core.config import ScoringConfig
    from igaming_platform_tpu.models.ensemble import make_score_fn
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import index_program
    from igaming_platform_tpu.serve import session_state as ss

    cfg = ScoringConfig()
    head_fn, init = HEADS[head].scores, HEADS[head].init
    head_params = None if head == "pattern" else init
    step = index_program.build(
        make_score_fn(cfg, "multitask", mesh=mesh), cfg, family="session",
        sketch=sketch, shadow=False, mesh=mesh, plan=plan,
        session=index_program.SessionSpec(
            head_fn, capacity, ss.default_events(), ss.default_min_events(),
            ss.default_flag_threshold()))
    args = _step_args(head_params, capacity, ring_rows,
                      1 if plan is None else plan.n_shards, state, repl, batch)
    return step.lower(*args)


def _compile_step(*args, **kwargs):
    return _lower_step(*args, **kwargs).compile()


# How the donated ring passes through a compiled step: as itself. A float32
# result of exactly the ring's size may be the argument, an element of the
# gather's or the scatter's loop state, a bitcast, the in-place update, or the
# fusion that wraps one; nothing else of a ring's size belongs in a step.
_RING_ITSELF = ("parameter", "get-tuple-element", "bitcast",
                "dynamic-update-slice", "scatter")
_INSTRUCTION = re.compile(
    r"\s*(ROOT\s+)?%?[\w.-]+ = (\w+)\[([\d,]*)\][^ ]* ([\w-]+)\(")


def _ring_sized(compiled, ring_elems: int, only: tuple = ()) -> list[str]:
    """The instructions of a compiled module whose result holds a ring's
    worth of elements or more, whatever its opcode (``convert``, ``copy``,
    ``fusion``...), type, shape or layout, other than the donated ring on its
    way through (``_RING_ITSELF``): what "a step moves O(batch) bytes of the
    ring" forbids (serve/session_state.py, "the ring's at-rest layout").
    With ``only``, every instruction of those opcodes and that size instead
    (a ``copy`` of some other array as large as ``ring_elems``)."""
    lines = compiled.as_text().splitlines()
    in_place, name = set(), None   # computations that end in an in-place update
    for line in lines:
        head = re.match(r"(?:ENTRY\s+)?%?([\w.-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
        m = _INSTRUCTION.match(line)
        if m and m.group(1) and m.group(4) in ("dynamic-update-slice", "scatter"):
            in_place.add(name)
    hits = []
    for line in lines:
        m = _INSTRUCTION.match(line)
        if not m or not m.group(3):
            continue
        _, dtype, dims, op = m.groups()
        elems = int(np.prod([int(d) for d in dims.split(",")]))
        if elems < ring_elems or (only and op not in only):
            continue
        wraps = re.search(r"calls=%?([\w.-]+)", line) if op == "fusion" else None
        itself = (not only and dtype == "f32" and elems == ring_elems
                  and (op in _RING_ITSELF
                       or (wraps is not None and wraps.group(1) in in_place)))
        if not itself:
            hits.append(line.strip()[:160])
    return hits


def _assert_in_place(compiled, ring_elems: int) -> None:
    mem = compiled.memory_analysis()
    assert _ring_sized(compiled, ring_elems) == []
    assert mem.temp_size_in_bytes < TEMP_LIMIT, mem
    assert mem.alias_size_in_bytes >= 4 * ring_elems, mem


def _kernels_under(text: str, capsys, head: str,
                    scope: str = "head/moe/experts") -> list[str]:
    """The Pallas calls under ``scope`` of a compiled step, the VMEM each
    asks for printed (its scoped region: what XLA keeps of its own in VMEM
    across the call lies under ``offset``)."""
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "custom-call(" in line
               and scope in line]
    asks = {}
    for line in kernels:
        name = re.match(r"\s*%(\w+?)(\.\d+)? = ", line).group(1)
        asked = re.search(r'scoped_memory_configs":\[\{"memory_space":"1",'
                          r'"offset":"(\d+)","size":"(\d+)"', line)
        offset, size = (int(g) for g in asked.groups()) if asked else (0, 0)
        assert offset + size <= 128 * 2**20, line[:200]  # the chip's VMEM
        asks.setdefault((name, size, offset), []).append(line)
    with capsys.disabled():
        for (name, size, offset), calls in asks.items():
            print(f"{head} {name} x {len(calls)}: {size} B of VMEM asked, "
                  f"above {offset} B that XLA holds")
    return kernels


def _assert_rows_come_in_by_the_kernel(text: str, pairs: int) -> None:
    """What ISSUE 44 found by reading this text: in the ``lfm2`` step the
    normed positions, made in VMEM, were copied out to HBM for XLA's
    gather into expert order (a ``copy-start`` of ``bf16[4096,2048]`` out
    of ``S(1)``; 33 ns a row on the chip against 6.4 in ``keye``'s step,
    whose source XLA happened to leave in VMEM). Now no sorted copy
    exists in either step: ``gate_up`` is handed the positions as 32-bit
    words, a row one tile, and brings its rows together itself."""
    assert f"bf16[{pairs},2048]" not in text
    assert not [line for line in text.splitlines()
                if "copy-start" in line and "bf16[4096,2048]" in line]
    gate_ups = [line for line in text.splitlines()
                if re.match(r"\s*%_gate_up(\.\d+)? = ", line)]
    assert len(gate_ups) == 4
    for line in gate_ups:
        assert "u32[4096,8,128]" in line and f"s32[{pairs}]" in line, line[:300]



def _assert_the_router_sorts_and_gathers_nothing(text: str, positions: int,
                                                 top_k: int) -> None:
    """``decoder_parts.route`` chooses by rounds of max-and-mask since PR 51:
    no ``sort`` of the compiled step carries its scope (each ``lax.top_k``
    was one: three a layer in ``ling``'s step), and the chosen scores are
    read back by compare-and-sum, so no gather yields ``f32[P, top_k]``
    (``take_along_axis`` lowered to one under the bare op_name ``gather``,
    without the scope, so it is told by its shape)."""
    lines = text.splitlines()
    assert "head/moe/route" in text
    assert not [line for line in lines if "head/moe/route" in line
                and (" sort(" in line or " gather(" in line)]
    assert not [line for line in lines if re.search(
        rf"= f32\[{positions},{top_k}\]\S* gather\(", line)]


@pytest.mark.parametrize("head", ["pattern", "transformer"])
def test_fused_session_step_writes_the_ring_in_place(topo, tpu_backend, head):
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.serve import session_state as ss

    one = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_step(head, CAPACITY, CAPACITY + 1, one, one)
    _assert_in_place(compiled, ss.ring_size(CAPACITY + 1, ss.default_events()))
    if head == "transformer":
        assert "tpu_custom_call" in compiled.as_text()


# The deep cell's step (`keye-deep128-insession`: 655,360 accounts of 128
# events, BATCH_SIZE=64) and what it may hold in temporaries: the model's own
# intermediates at 8,192 positions (read: 744,876,032 B, of it ``_down``'s
# ``f32[1048576,128]`` results 0.54 GB), which took the room of the parent's
# bfloat16 ring once the windows were gathered (2,072,092,160 B: PERF.md,
# section 6, PR 61). The small heads' steps at that depth hold next to nothing.
DEEP_ACCOUNTS, DEEP_EVENTS, DEEP_BATCH = 655_360, 128, 64


@pytest.mark.parametrize("head,temps", [
    ("keye", 800_000_000), ("pattern", TEMP_LIMIT),
    ("transformer", 2 * TEMP_LIMIT)])   # read: 68,979,712 B
def test_deep_window_step_reads_the_ring_by_the_batchs_rows_alone(
        topo, tpu_backend, capsys, monkeypatch, head, temps):
    """The 64-row step over 128-event windows at the deep cell's size: no
    instruction (``convert``, ``copy``, ``fusion``, whatever its shape or
    layout) yields a ring's worth of elements but the aliased ring itself,
    the ring is written in place, and the temporaries are the head's own.
    At the parent ``bfloat16-propagation`` (the TPU compiler's pass after its
    main fusion) read ``keye``'s first product back through the selects,
    ``take_along_axis`` and the gather's loop to the ring argument and put
    ``convert bf16[1006634496]`` there: 9.2 ms of a 47.2 ms step on the chip.
    The pass walks through an ``optimization_barrier`` (it rewrote the
    barrier's own type) and stops at a ``bitcast-convert``:
    ``session_state.windows_from_state`` hands the window over as 32-bit
    words behind one fence."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_EVENTS", str(DEEP_EVENTS))
    one = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_step(head, DEEP_ACCOUNTS, DEEP_ACCOUNTS + 1, one, one,
                             batch=DEEP_BATCH)
    ring = ss.ring_size(DEEP_ACCOUNTS + 1, DEEP_EVENTS)
    assert ring == 1_006_634_496
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{head} 64-row step of 128-event windows for a described "
              f"v5e: temporaries {mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert mem.temp_size_in_bytes < temps, mem
    assert f"f32[{ring}]" in compiled.as_text()   # the search had a ring to find


def test_backbone_step_fits_beside_the_state_and_groups_its_experts(
        topo, tpu_backend, capsys):
    """The fused step with the ``keye`` backbone in it, at the cell's size
    (5,242,880 accounts, one 256-row rung) and with the expert core the
    chip picks: still in place on the ring, its arguments are the state
    plus 5.0 GB of bfloat16 weights, its temporaries are not larger than
    the parent's (PR 36: 615,414,272 B; the float32 [32768, 2048] copy the
    return to position order gathered is gone), and the expert layer is
    three Pallas kernels a layer under the scope the trace reads them by
    (``_gate_up``, ``_down`` with its rows whole, ``_combine_rows``), with
    no XLA grouped product left. This is where the kernels' tiles and VMEM
    limits are proven to compile without a chip."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.serve import session_state as ss

    capacity = 5_242_880
    one = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_step("keye", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 9.7e9 < mem.argument_size_in_bytes < 9.9e9, mem
    # 396,118,528 B (PR 37); 615,414,272 B with XLA's gather and sum (PR 35)
    assert mem.temp_size_in_bytes <= 615_414_272, mem
    text = compiled.as_text()
    kernels = _kernels_under(text, capsys, "keye")
    for name in ("_gate_up", "_down", "_combine_rows"):
        calls = [k for k in kernels if re.match(rf"\s*%{name}(\.\d+)? = ", k)]
        assert len(calls) == 4, (name, kernels)
    assert len(kernels) == 12, kernels
    assert "%ragged-dot-none" not in text
    _assert_rows_come_in_by_the_kernel(text, 32768)


@pytest.mark.parametrize("events,batch,topk,parents_temps", [
    (16, 256, 2048, 394_860_544), (16, 64, 2048, 20_904_960),
    (128, 64, 2048, 744_876_032), (16, 64, 8, 20_904_960)],
    ids=["256-rows", "64-rows", "64-rows-of-128-events", "64-rows-topk-8"])
def test_backbone_step_runs_attention_where_the_projections_wrote(
        topo, tpu_backend, capsys, monkeypatch, events, batch, topk,
        parents_temps):
    """``keye``'s session step at both rungs of its cell (PR 47) and at the
    deep cell's 64 rows of 128 events: the core of attention is the window
    kernel's grouped form, one custom call a layer under ``head/attn`` (the
    scope ``head_attention_ms`` reads), on ``wq``'s float32 result as the
    product left it (channel-major: ``[heads x 128, P]``). Nothing of ``[b,
    heads, t, s]`` and no copy of ``q`` turned heads-first is left in the
    compiled module (at PR 47's parent: ``f32[256,4,16,16,8]`` scores and a
    copy of them, ``bf16[256,4,8,16,16]`` probabilities,
    ``f32[256,16,32,128]`` and ``bf16[256,16,32,128]`` copies of ``q``),
    and the step's temporaries stay within 10% of the parent's compile read
    (394,860,544 B at 256 rows, 20,904,960 at 64, 744,876,032 at 128
    events).

    Since PR 63 the indexer is traced only where it can drop a key. At the
    published ``topk`` 2048 over 16 or 128 events its selection is the
    causal mask, which the kernel builds from two iotas: the call has no
    ``s32[window, P]`` mask operand, and nothing under ``head/attn`` is the
    indexer's (no ``head/attn/indexer`` scope, no ``top_k``, no sort; the
    temporaries read here are 318,620,160 B at 256 rows where PR 47 read
    373,297,152, 17,929,216 at 64 rows where the mask's step holds
    21,526,016, and 735,391,232 at 128 events). At a ``topk`` under the
    window (8 of 16) the indexer is computed and its mask is the kernel's
    seventh operand, as at PR 47."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import keye_backbone, session_heads

    monkeypatch.setenv("SESSION_EVENTS", str(events))
    monkeypatch.setitem(session_heads.HEADS, "keye", session_heads._backbone(
        keye_backbone, keye_backbone.BackboneConfig(idx_topk=topk)))
    capacity = 5_242_880 if events == 16 else DEEP_ACCOUNTS
    positions = batch * events
    one = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_step("keye", capacity, capacity + 1, one, one, batch=batch)
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    with capsys.disabled():
        print(f"\nkeye {batch}-row step of {events}-event windows at topk "
              f"{topk} for a described v5e: temporaries "
              f"{mem.temp_size_in_bytes} B (the parent's {parents_temps})")
    assert mem.temp_size_in_bytes <= parents_temps * 1.1, mem
    cores = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and "head/attn/core" in line]
    assert len(cores) == 4, cores
    mask = f"s32[{events},{positions}]"
    for line in cores:
        assert re.match(r"\s*%_grouped_window_attention(\.\d+)? = "
                        rf"bf16\[4096,{positions}\]", line), line[:200]
        # q as ``wq`` accumulated it, k and v
        for operand in (f"f32[4096,{positions}]", f"bf16[{positions},512]",
                        f"bf16[512,{positions}]"):
            assert operand in line, (operand, line[:400])
        # the mask's rows over a tile: only where the indexer can drop a key
        assert (mask in line) is (topk < events), line[:400]
    under_attention = re.findall(r'op_name="jit\(_body\)/(head/attn/[^"]*)"', text)
    assert under_attention
    selecting = [name for name in under_attention
                 if "indexer" in name or "top_k" in name or "sort" in name]
    if topk < events:
        assert any(name.endswith("head/attn/indexer/top_k") for name in selecting)
    else:
        assert selecting == [] and mask not in text
    for gone in (f"[{batch},4,8,{events},{events}]", f"[{batch},4,{events},{events},8]",
                 f"[{batch},{events},32,128]", f"[{batch},{events},4,8,128]",
                 f"f32[{batch},{events},4096]"):
        assert gone not in text, gone


def test_latent_attention_step_fits_beside_the_state_and_holds_a_share(
        topo, tpu_backend, capsys):
    """The fused step with the ``pangu`` backbone in it, at the cell's size
    (3,145,728 accounts, one 256-row rung): in place on the ring, its
    arguments are the state plus 6.23 GB of bfloat16 weights, and its
    temporaries are not larger than the parent's (PR 36: 1,623,082,496 B):
    the held experts' pass is bounded by ``pass_rows`` (2,048 rows, as
    many as ``combine`` keeps in VMEM), not by the 32,768 pairs, so nothing
    of [32768, 7680] exists. One expert's gate and up are 63 MB a slot,
    over what the Pallas product kernels hold in VMEM, so the held experts'
    products are XLA's grouped product, inside the pass loop; the way back
    to position order is ``_combine_held``, once an expert layer. The core
    of attention is the other in-tree kernel, ``_window_attention``
    (ops/pallas/window_attention.py), once a layer, and no score array
    ``[256, 128, 16, 16]`` is left in the step."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.expert_layer import pass_rows
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    capacity = 3_145_728
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["pangu"].config
    compiled = _compile_step("pangu", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\npangu step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 9.0e9 < mem.argument_size_in_bytes < 9.1e9, mem
    assert mem.temp_size_in_bytes <= 1_623_082_496, mem
    pairs = BATCH * ss.default_events() * cfg.top_k
    assert pass_rows(pairs, cfg.held_experts, cfg.experts, cfg.hidden) == 2048
    text = compiled.as_text()
    assert f"[{pairs},{cfg.hidden}]" not in text
    assert "ragged-dot" in text and "head/moe/experts" in text
    # XLA's grouped product is a Mosaic custom call of its own
    # (`%ragged-dot-none`); the in-tree kernels' calls carry `pallas_call`
    # in their op_name: of ops/pallas/grouped_experts only the way back is
    # there, under the expert scope, and the core of attention
    # (ops/pallas/window_attention) under `head/attn/core`.
    # (Calls, not names: the module's table of function names is the
    # process's, and holds `_gate_up` once the keye step was traced in it.)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len([c for c in calls
                if re.match(r"\s*%ragged-dot-none(\.\d+)? = ", c)]) == 12, calls
    in_tree = [c for c in calls if "pallas_call" in c]
    back = [c for c in in_tree if "_combine_held" in c]
    core = [c for c in in_tree if "_window_attention" in c]
    assert len(back) == cfg.layers - cfg.dense_layers == 4, in_tree
    assert all("head/moe/experts" in c for c in back), back
    assert len(core) == cfg.layers == 5, in_tree
    assert all("head/attn/core" in c for c in core), core
    assert len(in_tree) == 9, in_tree
    scores = f"[{BATCH},{cfg.heads},{ss.default_events()},{ss.default_events()}]"
    assert scores == "[256,128,16,16]" and scores not in text
    _assert_the_router_sorts_and_gathers_nothing(
        text, BATCH * ss.default_events(), cfg.top_k)


def test_short_convolution_step_fits_beside_the_state_and_groups_its_experts(
        topo, tpu_backend, capsys):
    """The fused step with the ``lfm2`` backbone in it, at the cell's size
    (5,242,880 accounts, one 256-row rung): in place on the ring, its
    arguments are the state plus 5.13 GB of weights, and the expert layer
    is the three Pallas kernels the ``keye`` head runs, at a second shape:
    64 experts of width 1,536 and 4 a position where they were written for
    128 of 768 and 8 (``_gate_up``'s and ``_down``'s column tiles,
    ``_schedule``'s visits and ``_combine_rows``'s ``k`` all take other
    values), once a layer under the scope the trace reads them by, with no
    XLA grouped product left. The convolution's taps are no kernel and no
    product on the MXU: three shifted elementwise products a layer. Code size
    and temporaries are printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    capacity = 5_242_880
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["lfm2"].config
    compiled = _compile_step("lfm2", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nlfm2 step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 9.8e9 < mem.argument_size_in_bytes < 10.0e9, mem
    assert mem.temp_size_in_bytes <= 615_414_272, mem  # keye's bound
    text = compiled.as_text()
    kernels = _kernels_under(text, capsys, "lfm2")
    moe = len(cfg.layer_types) - cfg.dense_layers
    for name in ("_gate_up", "_down", "_combine_rows"):
        calls = [k for k in kernels if re.match(rf"\s*%{name}(\.\d+)? = ", k)]
        assert len(calls) == moe == 4, (name, kernels)
    assert len(kernels) == 12, kernels
    assert "%ragged-dot-none" not in text
    _assert_rows_come_in_by_the_kernel(text, 16384)
    # the taps are elementwise work under their scope, never a product
    assert "head/conv/taps" in text and "head/attn" in text
    assert not [line for line in text.splitlines()
                if " convolution(" in line and "head/conv/taps" in line]
    _assert_the_router_sorts_and_gathers_nothing(
        text, BATCH * ss.default_events(), cfg.top_k)


def test_state_space_step_fits_beside_the_state_and_holds_no_state(
        topo, tpu_backend, capsys):
    """The fused step with the ``falconh1`` backbone in it, at the cell's
    size (5,242,880 accounts, one 256-row rung): in place on the ring, its
    arguments are the state plus 3.44 GB of weights. Every layer has both
    mixers and the MLP under the scopes the trace reads them by. Since PR 55
    everything of a state-space mixer between its two projections (the taps
    with their bias, ``silu``, ``softplus``, the decay, the dual form over
    one chunk, the gate and the grouped norm) is ONE Pallas call a layer,
    ``_ssd_window`` (ops/pallas/ssd_window.py) under ``head/ssm/scan``, over
    the in-projection's own ``[4096, 9248]`` result and writing the
    out-projection's bfloat16 operand: no ``head/ssm/conv`` and no
    ``head/ssm/gate`` is left, no copy of the projection's result, none of
    the einsum form's layouts, no array of a state's size (32 x 128 x 256 a
    window), and the taps are no ``convolution``. There is no expert layer:
    no grouped product. Code size and temporaries are printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    capacity = 5_242_880
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["falconh1"].config
    compiled = _compile_step("falconh1", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nfalconh1 step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 8.1e9 < mem.argument_size_in_bytes < 8.3e9, mem
    assert mem.temp_size_in_bytes <= FALCONH1_TEMPS_256, mem
    text = compiled.as_text()
    for scope in ("head/embed", "head/ssm/in", "head/ssm/scan", "head/ssm/out",
                  "head/attn", "head/mlp/dense", "head/score"):
        assert scope in text, scope
    for scope in ("head/ssm/conv", "head/ssm/gate"):
        assert scope not in text, scope
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line
             and "custom-call(" in line]
    assert len(calls) == cfg.layers and "%ragged-dot" not in text
    assert all("_ssd_window" in line and "head/ssm/scan" in line for line in calls)
    batch = BATCH * ss.default_events()
    wide = sum(cfg.segments)
    # the kernel reads the product's result where it lies: nothing copies it
    assert f"f32[{batch},{wide}]" in text
    assert not [line for line in text.splitlines()
                if f"f32[{batch},{wide}]" in line.split("=")[0] and " copy(" in line]
    for layout in (f"[{BATCH},16,32,128]", f"[{BATCH},16,16,32]",
                   f"[{BATCH},32,16,16]", f"f32[{batch},4096]"):
        assert layout not in text, layout
    assert not [line for line in text.splitlines() if " convolution(" in line
                and "head/ssm/conv" in line]
    state = f"{cfg.ssm_heads},{cfg.ssm_head_dim},{cfg.ssm_state}]"
    assert "32,128,256]" == state and state not in text


def test_delta_rule_step_fits_beside_the_state_and_holds_no_state(
        topo, tpu_backend, capsys):
    """The fused step with the ``ling`` backbone in it, at the cell's size
    (3,145,728 accounts, the 256-row rung): in place on the ring, its
    arguments are the state plus 5.53 GB of weights. Every part is under
    the scope the trace reads it by; the delta rule is its one-chunk form,
    so no array of a state's size (32 x 128 x 128 a window) is in the
    module, and the taps are no ``convolution``. Since PR 50 everything of
    a KDA mixer between its projections and its output gate (the taps,
    ``silu``, the L2 norm, the decay, the delta core, the head norm) is ONE
    Pallas call a layer, ``_delta_window`` (ops/pallas/delta_window.py)
    under ``head/kda/core``, over the projections' own ``[4096, 4096]``
    results: six calls, the scopes ``head/kda/conv`` and ``head/kda/gate``
    inside them (``test_delta_core_is_one_call_a_layer`` holds a mixer
    alone to the layouts that are gone). Hidden 2560 is not a
    multiple of 2,048, so the held experts' rows are gathered for the
    Pallas grouped kernels (``_gate_up``, ``_down``) inside the pass loop,
    their way back ``_combine_held``, once an expert layer; the
    latent-attention layer's core is the einsums (interleaved rotary
    pairs), so no ``_window_attention``. Code, temporaries and arguments
    are printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    capacity = 3_145_728
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["ling"].config
    compiled = _compile_step("ling", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nling step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 8.3e9 < mem.argument_size_in_bytes < 8.5e9, mem
    assert mem.temp_size_in_bytes <= 2 * 2**30, mem
    text = compiled.as_text()
    for scope in ("head/embed", "head/kda/proj", "head/kda/core", "head/kda/out",
                  "head/attn/core", "head/attn/gate", "head/mlp/dense",
                  "head/moe/route", "head/moe/shared", "head/moe/experts",
                  "head/score"):
        assert scope in text, scope
    # as an instruction, not as a substring: the module's table of source
    # files names ``tests/test_window_attention.py`` where this worker traced
    # a shared jitted function from that file first
    assert not [line for line in text.splitlines()
                if re.match(r"\s*%_window_attention(\.\d+)? = ", line)]
    assert not [line for line in text.splitlines() if " convolution(" in line
                and ("head/kda/conv" in line or "head/kda/core" in line)]
    state = f"{cfg.heads},{cfg.head_dim},{cfg.head_dim}]"
    assert "32,128,128]" == state and state not in text
    kernels = _kernels_under(text, capsys, "ling")
    assert kernels, "the held experts' products run as no kernel"
    delta = [line for line in text.splitlines() if "tpu_custom_call" in line
             and "custom-call(" in line and "_delta_window" in line]
    assert len(delta) == 6 and all("head/kda/core" in line for line in delta)
    _assert_the_router_sorts_and_gathers_nothing(
        text, BATCH * ss.default_events(), cfg.top_k)
    # the einsum core's float32 passes over [256, 16, 32, 128] and the
    # substitution's row stacks are gone
    assert mem.temp_size_in_bytes <= LING_TEMPS_256, (
        f"{mem.temp_size_in_bytes} B of temporaries; 441004032 since the "
        f"router sorts nothing (PR 51), 479243264 with the delta kernel (PR "
        f"50), 984582656 with the einsum core (PR 49)")


def test_hyper_connected_step_fits_beside_the_state_and_keeps_its_scopes(
        topo, tpu_backend, capsys):
    """The fused step with the ``xing`` backbone in it, at the cell's size
    (3,145,728 accounts, the 256-row rung): in place on the ring, its
    arguments are the state plus 6.22 GB of weights, and the four float32
    streams (235 MB a copy) are what its temporaries are made of. The three
    ``head/hc/*`` scopes, attention's and the expert layer's are all in the
    compiled module; the core of attention is ``_window_attention`` once a
    layer at ``pangu``'s head widths; every expert is held and a window's
    padding is left out, so the pairs go through the share's pass loop:
    hidden 3584 is no multiple of 2,048, so the rows are gathered for the
    Pallas grouped kernels (``_gate_up``, ``_down``) and the way back is
    ``_combine_held``, once an expert layer; ``pass_rows`` is what
    ``combine`` keeps in VMEM (4,608 rows of 3,584 float32). No ``[P, n,
    n]`` array with the two small axes minor is in it: the maps lie
    positions along the lanes. Code, temporaries and arguments are
    printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.expert_layer import pass_rows
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    capacity = 3_145_728
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["xing"].config
    compiled = _compile_step("xing", capacity, capacity + 1, one, one)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nxing step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 9.0e9 < mem.argument_size_in_bytes < 9.1e9, mem
    assert mem.temp_size_in_bytes <= XING_TEMPS_256, mem
    text = compiled.as_text()
    for scope in ("head/embed", "head/hc/maps", "head/hc/write",
                  "head/attn/core", "head/mlp/dense", "head/moe/route",
                  "head/moe/shared", "head/moe/experts", "head/exit"):
        assert scope in text, scope
    assert "head/hc/read" not in text  # the read is the maps' pass
    positions = BATCH * ss.default_events()
    pairs = positions * cfg.top_k
    assert pass_rows(pairs, cfg.experts, cfg.experts, cfg.hidden) == 4608
    assert f"[{pairs},{cfg.hidden}]" not in text
    assert f"f32[{positions},{cfg.streams},{cfg.streams}]" not in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and "pallas_call" in line]
    found = {}
    for line in calls:
        name = re.match(r"\s*%([\w-]+?)(\.\d+)? = ", line).group(1)
        found[name] = found.get(name, 0) + 1
    moe = cfg.layers - cfg.dense_layers
    assert found == {"_window_attention": cfg.layers, "_gate_up": moe,
                     "_down": moe, "_combine_held": moe,
                     **XING_STREAM_CALLS}, found
    assert all("head/attn/core" in c for c in calls if "_window_attention" in c)
    assert _kernels_under(text, capsys, "xing")
    _assert_the_residual_path_is_two_passes(text, capsys, positions, cfg)
    _assert_the_router_sorts_and_gathers_nothing(text, positions, cfg.top_k)


def test_deep_window_step_sweeps_its_key_blocks_in_one_kernel_for_both_kinds(
        topo, tpu_backend, capsys, monkeypatch):
    """The fused step with the ``mellum`` backbone in it, at its cell's size
    (20,480 accounts of 4,096 events, the 2-row step: 8,192 positions, four
    layers at the published widths): in place on the 4.03 GB ring, its
    arguments the state plus 3.34 GB of weights. The core of attention is
    ``_block_attention`` (ops/pallas/block_attention.py) once a layer, three
    times under ``head/attn/window/core`` and once under
    ``head/attn/full/core``, on ``wq``'s float32 result as the product left
    it; nothing of ``[b, g, j, t, s]`` at a whole window's keys and nothing
    of a window's square is in the module (as einsums a layer's scores would
    be ``f32[2,4,8,4096,4096]``, 4.3 GB). The expert layer is three kernels
    a layer at 65,536 pairs (~1,024 rows an expert): the two grouped
    products and, since PR 58, the way back (``_combine_rows`` over rows at
    a pitch of 24 sublanes, hidden 2304 being 18 lane tiles); and the router
    sorts and gathers nothing. Code, temporaries and arguments are
    printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_EVENTS", "4096")
    capacity, batch = 20_480, 2
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["mellum"].config
    compiled = _compile_step("mellum", capacity, capacity + 1, one, one,
                             batch=batch)
    ring = ss.ring_size(capacity + 1, 4096)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nmellum 2-row step of 4,096-event windows for a described "
              f"v5e: code {mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 7.3e9 < mem.argument_size_in_bytes < 7.5e9, mem
    # 1.02 GB (PR 58; 1.31 while XLA's way back held a gathered float32 copy
    # of the experts' results, 0.60 GB, beside the results)
    assert mem.temp_size_in_bytes < 1.2e9, mem
    text = compiled.as_text()
    positions = batch * 4096
    cores = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and re.match(r"\s*%_block_attention(\.\d+)? = ", line)]
    assert len(cores) == cfg.layers, cores
    for line in cores:
        assert re.match(rf"\s*%_block_attention(\.\d+)? = bf16\[{positions},4096\]",
                        line), line[:200]
        for operand in (f"f32[{positions},4096]", f"bf16[{positions},512]"):
            assert operand in line, (operand, line[:400])
    assert sum("head/attn/window/core" in c for c in cores) == 3
    assert sum("head/attn/full/core" in c for c in cores) == 1
    for gone in ("4096,4096]", f"[{batch},4,8,"):
        assert gone not in text, gone
    kernels = _kernels_under(text, capsys, "mellum")
    # hidden 2304 is 18 lane tiles: ``down`` writes its rows whole at a
    # pitch of 24 sublanes, three (8, 128) tiles (``f32[1572864,128]``, a
    # bitcast away from ``[65536,24,128]``), and the way back to position
    # order is ``_combine_rows`` once a layer, a DMA an owed row
    for name, count in (("_gate_up", cfg.layers), ("_down", cfg.layers),
                        ("_combine_rows", cfg.layers)):
        calls = [k for k in kernels if re.match(rf"\s*%{name}(\.\d+)? = ", k)]
        assert len(calls) == count, (name, kernels)
    pairs, pitch = positions * cfg.top_k, 24
    for line in kernels:
        if re.match(r"\s*%_down(\.\d+)? = ", line):
            assert f" = f32[{pairs * pitch},128]" in line, line[:200]
        if re.match(r"\s*%_combine_rows(\.\d+)? = ", line):
            assert f" = f32[{positions},{cfg.hidden}]" in line, line[:200]
    # nothing of XLA's way back is left: no gathered float32 copy of the
    # results (``ys[rank]``) nor any other array of their plain shape, and
    # no re-layout copy of the results at their pitch
    assert f"f32[{pairs},{cfg.hidden}]" not in text
    results = pairs * pitch * 128
    assert _ring_sized(compiled, results, only=("copy",)) == []
    assert "%ragged-dot-none" not in text
    _kernels_under(text, capsys, "mellum", scope="head/attn")
    _assert_the_router_sorts_and_gathers_nothing(text, positions, cfg.top_k)


def test_narrowing_step_scans_in_one_kernel_a_layer_and_holds_the_model_whole(
        topo, tpu_backend, capsys, monkeypatch):
    """The fused step with the ``phi4flash`` backbone in it, at its cell's
    size (16,384 accounts of 2,048 events, the 2-row step: 4,096 positions,
    all 32 layers at the published widths): in place on the 1.61 GB ring, its
    arguments the state plus 6.68 GB of weights. The recurrence is
    ``_selective_scan`` (ops/pallas/selective_scan.py) once a Mamba layer,
    nine times under ``head/ssm/scan``, on ``x`` and ``dt`` position-major as
    their products left them; nothing of ``[positions, channels, state]``
    (1.34 GB a tensor) is in the module, nothing of a window's ``2048,2048``
    square, and past layer 17 no product over all 4,096 positions: the
    second half's matrices meet 2 rows. Code, temporaries and arguments are
    printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import phi4flash_backbone
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_EVENTS", "2048")
    capacity, batch = 16_384, 2
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["phi4flash"].config
    compiled = _compile_step("phi4flash", capacity, capacity + 1, one, one,
                             batch=batch)
    ring = ss.ring_size(capacity + 1, 2048)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nphi4flash 2-row step of 2,048-event windows for a described "
              f"v5e: code {mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 8.2e9 < mem.argument_size_in_bytes < 8.4e9, mem
    assert mem.temp_size_in_bytes < 1.5e9, mem
    text = compiled.as_text()
    positions = batch * 2048
    scans = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and re.match(r"\s*%_selective_scan(\.\d+)? = ", line)]
    assert len(scans) == 9 == phi4flash_backbone.kinds_of(cfg).count("ssm"), scans
    wide = f"f32[{positions},{cfg.ssm_width}]"
    for line in scans:
        assert "head/ssm/scan" in line
        assert re.match(rf"\s*%_selective_scan(\.\d+)? = {re.escape(wide)}", line), line[:200]
        assert line.count(wide) >= 3, line[:400]          # y, x and dt
        assert f"f32[{positions // 8},16,8]" in line, line[:400]   # B and C
    _kernels_under(text, capsys, "phi4flash", scope="head/ssm/scan")
    for gone in (f",{cfg.ssm_width},16]", "2048,2048]",
                 f"[{positions},{cfg.ssm_width},"):
        assert gone not in text, gone
    # the second half meets 2 rows: no product of its scopes has 4,096 rows
    second = [line for line in text.splitlines()
              if "head/cross" in line and " convolution(" in line]
    assert second and not [line for line in second
                           if f"[{positions}," in line.split(" convolution(")[0]]


def test_two_depth_step_reads_the_module_at_one_position_a_row(
        topo, tpu_backend, capsys, monkeypatch):
    """The fused step with the ``kexaone`` backbone in it, at its cell's size
    (24,576 accounts of 2,048 events, the 2-row step: 4,096 positions, five
    layers and the multi-token-prediction module at the published widths): in
    place on the 2.42 GB ring, its arguments the state plus 5.6 GB of weights.
    The stack's cores are ``_block_attention`` (ops/pallas/block_attention.py)
    once a layer, four under ``head/attn/window/core`` and one under
    ``head/attn/full/core``, at 64 / 8 heads: the sliding layers' in the
    one-visit form (a band of 128 is under half a block of 512: 16 query
    blocks of 128 rows a window, each against one slab of 256 keys), the full
    layer's the sweep of 10 of 16 blocks of 512, read off the pairs each call
    says it scores; nothing of a window's ``2048,2048`` square is in the
    module. Of the module the join and the
    ``K, V`` product meet all 4,096 positions and no other product under
    ``head/mtp`` does. The expert products are ``ragged-dot`` (two slots of a
    6144 x 2048 gate and up are 96 MiB, past what ``grouped_experts.supports``
    grants: ``pangu``'s case) and the way back ``_combine_held`` in each
    stack layer's pass loop. Code, temporaries and arguments are printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import decoder_parts
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_EVENTS", "2048")
    capacity, batch = 24_576, 2
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["kexaone"].config
    decoder_parts.announce_core.cache_clear()
    compiled = _compile_step("kexaone", capacity, capacity + 1, one, one,
                             batch=batch)
    # what ``/debug/sessionz``'s ``head_cores`` would say of this step
    said = decoder_parts.announced_cores()
    assert said["attention core (window)"] == (
        "pallas-blocks (grouped 64/8 of 128, window 2048, band=128: one visit "
        "of 256 keys a 128-row block) (backend=tpu)")
    assert said["attention core (full)"] == (
        "pallas-blocks (grouped 64/8 of 128, window 2048 in blocks of 512, "
        "band=None: 10 of 16 key blocks; no rotary: unit cos, zero sin) "
        "(backend=tpu)")
    ring = ss.ring_size(capacity + 1, 2048)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nkexaone 2-row step of 2,048-event windows for a described "
              f"v5e: code {mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 7.9e9 < mem.argument_size_in_bytes < 8.1e9, mem
    assert mem.temp_size_in_bytes < 0.9e9, mem  # 0.68 GB at PR 65
    text = compiled.as_text()
    positions = batch * 2048
    qw, kvw = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    cores = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and re.match(r"\s*%_block_attention(\.\d+)? = ", line)]
    assert len(cores) == cfg.layers, cores
    for line in cores:
        assert re.match(rf"\s*%_block_attention(\.\d+)? = bf16\[{positions},{qw}\]",
                        line), line[:200]
        for operand in (f"f32[{positions},{qw}]", f"bf16[{positions},{kvw}]"):
            assert operand in line, (operand, line[:400])
    assert sum("head/attn/window/core" in c for c in cores) == 4
    assert sum("head/attn/full/core" in c for c in cores) == 1
    assert not [c for c in cores if "head/mtp" in c]
    # a call's grid, by the pairs it scores (one ``exp`` each): windows x
    # heads x (16 blocks of 128 rows x a slab of 256 keys | 10 blocks of
    # 512 x 512), and no float32 accumulator beside the one-visit form's
    # stacked queries in VMEM
    for c in cores:
        pairs = 16 * 128 * 256 if "window/core" in c else 10 * 512 * 512
        assert f'"transcendentals":"{batch * cfg.heads * pairs}"' in c, c[-900:]
    assert "2048,2048]" not in text
    # the module: two products over every position (the join, and K with V),
    # every other one over the 2 rows that are read
    module = [line.split(" convolution(")[0] for line in text.splitlines()
              if "head/mtp" in line and " convolution(" in line]
    whole = [line for line in module if f"[{positions}," in line]
    assert module and 1 <= len(whole) <= 3, whole
    assert not [line for line in whole if f"[{positions},{qw}]" in line], whole
    _kernels_under(text, capsys, "kexaone", scope="head/attn")
    back = _kernels_under(text, capsys, "kexaone")
    assert len(back) >= 4 and all("_combine_held" in line for line in back)
    assert "ragged-dot" in text
    _assert_the_router_sorts_and_gathers_nothing(text, positions, cfg.top_k)


def test_double_layer_step_holds_no_square_of_a_window_and_narrows_its_last_half(
        topo, tpu_backend, capsys, monkeypatch):
    """The fused step with the ``longcat`` backbone in it, at its cell's size
    (16,384 accounts of 2,048 events, the 2-row step: 4,096 positions, four
    double layers at the published widths with 16 of 64 heads and 8 of 512
    experts held): in place on the 1.61 GB ring, its arguments the state plus
    6.6 GB of weights. The eight latent attentions turn interleaved rotary
    pairs, which the window kernel does not; since PR 69 the seven whole
    cores are ``_latent_block_attention`` (ops/pallas/block_attention.py,
    which turns the pairing it is told) once an attention under
    ``head/attn/[01]/core``, on ``Wq_b``'s float32 result as the product left
    it: nothing of a window's ``2048,2048`` square, at 16 heads or at one,
    and no ``[.., 512, 2048]`` float32 scores of a query block are among the
    step's temporaries. The last one's one query a row stays einsums. Of the
    last layer's
    second attention the ``K, V`` products meet all 4,096 positions and no
    other product of its scope does; the expert branch's scopes are all
    there, the held experts' products ``ragged-dot`` (6144 x 2048 slots:
    ``kexaone``'s case), and the router sorts and gathers nothing. Code,
    temporaries and arguments are printed."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import decoder_parts
    from igaming_platform_tpu.models.session_heads import HEADS
    from igaming_platform_tpu.serve import session_state as ss

    monkeypatch.setenv("SESSION_EVENTS", "2048")
    capacity, batch = 16_384, 2
    one = SingleDeviceSharding(topo.devices[0])
    cfg = HEADS["longcat"].config
    decoder_parts.announce_core.cache_clear()
    compiled = _compile_step("longcat", capacity, capacity + 1, one, one,
                             batch=batch)
    said = decoder_parts.announced_cores()
    assert said["attention core"] == (
        "pallas-blocks (latent, 16 heads of 128 + 64 / 128, interleaved rotary "
        "pairs, window 2048 in blocks of 512, band=None: 10 of 16 key blocks) "
        "(backend=tpu)")
    assert said["attention core (narrowed)"].startswith(
        "xla-einsum, one query a row (window 2048, 16 heads")
    ring = ss.ring_size(capacity + 1, 2048)
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nlongcat 2-row step of 2,048-event windows for a described "
              f"v5e: code {mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, arguments "
              f"{mem.argument_size_in_bytes} B")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert 8.1e9 < mem.argument_size_in_bytes < 8.4e9, mem
    # 0.58 GB since PR 69 (0.68 at PR 68, whose einsum cores stood a query
    # block's float32 scores in HBM)
    assert mem.temp_size_in_bytes < 0.8e9, mem
    text = compiled.as_text()
    positions = batch * 2048
    # the seven whole cores: one kernel an attention, on the projections'
    # results as they lie (q float32, kvb and k_rope rounded)
    cores = [line for line in _kernels_under(text, capsys, "longcat",
                                             scope="head/attn")
             if re.match(r"\s*%_latent_block_attention(\.\d+)? = ", line)]
    assert len(cores) == 2 * cfg.layers - 1, cores
    qk, kvw = cfg.nope_dim + cfg.rope_dim, cfg.nope_dim + cfg.v_dim
    for line in cores:
        assert re.search(r"head/attn/[01]/core", line), line[:300]
        assert re.match(rf"\s*%_latent_block_attention(\.\d+)? = "
                        rf"bf16\[{positions},{cfg.heads * cfg.v_dim}\]", line), line[:200]
        for operand in (f"f32[{positions},{cfg.heads * qk}]",
                        f"bf16[{positions},{cfg.heads * kvw}]",
                        f"bf16[{positions},{cfg.rope_dim}]"):
            assert operand in line, (operand, line[:400])
    assert sum("head/attn/0/core" in c for c in cores) == cfg.layers
    assert sum("head/attn/1/core" in c for c in cores) == cfg.layers - 1
    # no query block's scores against its window's keys stand in HBM
    assert not re.search(r"f32\[(\d+,)*512,2048\]", text)
    # no [heads, t, t] array of a whole window, nor one head's square
    # (a pass of the held experts is 2,048 rows of width 2,048: not a window's)
    assert not re.search(r"\[(\d+,)+2048,2048\]", text)
    assert not [line for line in text.splitlines()
                if "head/attn" in line and "2048,2048]" in line]
    for scope in ("head/attn/0/q", "head/attn/0/kv", "head/attn/0/core",
                  "head/attn/0/out", "head/attn/1/core", "head/mlp/dense",
                  "head/moe/route", "head/moe/experts", "head/moe/zero"):
        assert scope in text, scope
    # the last layer's second attention: K and V over every position, the
    # queries and Wo over the 2 rows that are read. A product's result names
    # its rows first; the last attention's are told from the seven whole
    # ones' by their count.
    products = [line.split(" convolution(")[0] for line in text.splitlines()
                if "head/attn/1/" in line and " convolution(" in line]
    queries = [line for line in products
               if f",{cfg.heads * (cfg.nope_dim + cfg.rope_dim)}]" in line]
    assert [f"[{positions}," in line for line in queries].count(False) >= 1, queries
    assert "ragged-dot" in text
    _assert_the_router_sorts_and_gathers_nothing(text, positions, cfg.top_k)


# The stream kernels of the ``xing`` step (ops/pallas/hyper_streams.py): every
# hyper-connected sublayer of the five layers held, the first among them.
XING_STREAM_CALLS = {"_streams_maps_read": 10, "_streams_write": 10}


def _assert_the_residual_path_is_two_passes(text, capsys, positions, cfg):
    """Each stream kernel under its ``head/hc/*`` scope (the VMEM it asks
    printed), and no fusion left under ``head/hc`` that reads all the
    streams: XLA's passes over them are out of the step."""
    under = _kernels_under(text, capsys, "xing", "head/hc/")
    assert len(under) == sum(XING_STREAM_CALLS.values())
    for line in under:
        scope = "head/hc/maps" if "_streams_maps_read" in line else "head/hc/write"
        assert scope in line, line[:300]
    stream = f"f32[{positions},{cfg.hidden}]"
    over_all = [line for line in text.splitlines()
                if " fusion(" in line and "head/hc" in line
                and line.split(" fusion(", 1)[1].count(stream) >= cfg.streams]
    assert not over_all, over_all[0][:400]


# What the ``xing`` step holds in temporaries at the 256-row rung since PR 53:
# the four float32 streams (235 MB) once, written in place by the stream
# kernels, beside a sublayer's own intermediates (931,794,432 B at PR 52,
# where XLA's passes held the streams four times over). Read: 488,598,016 B;
# the bound is four times the 64-row rung's 122,904,576 B, which the rung's
# test holds under a quarter of it (``phi`` turned columns-first, 1.4 MB a
# sublayer, does not shrink with the rung; 122,840,064 B until PR 61 stood
# the window's words behind their fence, 64 KB at 64 rows; 122,904,576 B
# until PR 67 made the chunk's columns temporaries, unpacked from the one
# packed argument behind their fence, where each was an argument: 323,072 B
# at 64 rows, and the 256-row step reads 488,852,992 B).
XING_TEMPS_256 = 4 * 123_227_648


# What the ``falconh1`` step holds in temporaries at the 256-row rung since PR
# 55 (read: 627,768,832 B since PR 61, the window's words behind their fence
# 32 KB of it, 627,736,576 before; 631,744,000 at PR 46 with the float32
# passes between the mixer's projections, which were never the peak: the MLP's
# two ``[4096, 21504]`` float32 products are); the 64-row rung reads
# 139,813,888 B (142,620,160 at PR 46). Since PR 67 628,088,320 B and
# 140,233,728 B: the packed chunk's unpacked columns are temporaries where
# each was an argument (319,488 B at 256 rows).
FALCONH1_TEMPS_256 = 628_088_320


# What the ``ling`` step holds in temporaries at the 256-row rung since PR 51,
# the routers' sorts out of it (479,243,264 B at PR 50 with the delta kernel,
# 984,582,656 with the einsum core, PR 49); the 64-row rung reads 52,811,776 B
# (54,972,928 at PR 50, 132,454,912 at PR 49), and the step's code 26.4 / 22.8
# MB (34.3 MB at the 64-row rung at PR 49: the unrolled substitution is out
# of the program). Since PR 67 441,259,008 B and 53,392,896 B (441,004,032
# until then): the packed chunk's unpacked columns, as in ``falconh1``'s.
LING_TEMPS_256 = 441_259_008


@pytest.mark.parametrize("batch", [256, 64])
def test_delta_core_is_one_call_a_layer(topo, tpu_backend, batch):
    """One KDA mixer compiled alone at each rung's positions (the step's
    one latent-attention layer has score stacks of the same shapes; the
    steps themselves are held to six ``_delta_window`` calls above and
    below): one custom call, under the scope ``core`` where ``kda_core_ms``
    reads it, and nothing of the einsum core's layouts in the compiled
    text: no ``[b, 32, 16, 16]`` scores or stacks, no ``[512, 8, 32, 128]``
    re-layout of the projections' results or of the core's, no ``[b, 16,
    32, 128]`` or heads-first ``[b, 32, 16, 128]`` copy of q, k, v or g;
    between the projections and ``Wo`` only ``[P, 4096]``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import ling_backbone as lb

    one = SingleDeviceSharding(topo.devices[0])
    cfg = lb.LingConfig()
    layer = jax.eval_shape(
        lambda: lb.init_backbone(jax.random.key(0), cfg))["layers"][0]
    layer = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one), layer)
    u = _spec((batch * 16, cfg.hidden), jnp.float32, one)
    text = jax.jit(lambda u, layer: lb.kda_mixer(u, layer, cfg, 16)).lower(
        u, layer).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line
             and "custom-call(" in line and "_delta_window" in line]
    assert len(calls) == 1 and "/core/" in calls[0]
    for layout in (f"[{batch},32,16,16]", f"[{batch},32,16,128]",
                   f"[{2 * batch},8,32,128]", f"[{batch},16,32,128]",
                   f"[{batch},16,4096]"):
        assert layout not in text, layout
    assert f"f32[{batch * 16},4096]" in text


@pytest.mark.parametrize("batch", [256, 64])
def test_state_space_core_is_one_call_a_layer(topo, tpu_backend, batch):
    """One state-space mixer compiled alone at each rung's positions: three
    device programs in a row, the in-projection with its multipliers fused
    in, one custom call under the scope ``scan`` where ``ssm_scan_ms`` reads
    it, and the out-projection reading the call's bfloat16 result. The
    in-projection's result is written positions-major and read where it
    lies: no ``copy`` of it (handed ``dt`` as a transposed slice, XLA wrote
    the product channels-major and copied all 151 MB of it for the call:
    ``dt`` is the kernel's fifth block view for that reason), no float32
    slice of it, none of the einsum form's ``[b, 16, ...]`` layouts, and no
    float32 ``[P, 4096]`` between the projections."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.models import falconh1_backbone as fb

    one = SingleDeviceSharding(topo.devices[0])
    cfg = fb.FalconH1Config()
    layer = jax.eval_shape(
        lambda: fb.init_backbone(jax.random.key(0), cfg))["layers"][0]
    layer = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one), layer)
    positions, wide = batch * 16, sum(cfg.segments)
    u = _spec((positions, cfg.hidden), jnp.float32, one)
    text = jax.jit(lambda u, layer: fb.ssm_mixer(u, layer, cfg, 16)).lower(
        u, layer).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line
             and "custom-call(" in line]
    assert len(calls) == 1 and "_ssd_window" in calls[0] and "/scan/" in calls[0]
    assert f"bf16[{positions},{cfg.ssm_width}]" in calls[0].split("=")[1]
    made = [line for line in text.splitlines()
            if f" f32[{positions},{wide}]" in line.split("(")[0]]
    assert made and all("{1,0:" in line and " copy(" not in line
                        and " slice(" not in line for line in made), made
    for layout in (f"[{batch},16,32,128]", f"[{batch},16,16,32]",
                   f"[{batch},16,5120]", f"[{batch},16,2,256]",
                   f"f32[{positions},{cfg.ssm_width}]"):
        assert layout not in text, layout


@pytest.mark.parametrize("head,capacity,temps_256,in_tree", [
    ("pangu", 3_145_728, 950_890_496,
     {"_window_attention": 5, "_combine_held": 4, "ragged-dot-none": 12}),
    ("falconh1", 5_242_880, FALCONH1_TEMPS_256, {"_ssd_window": 4}),
    ("keye", 5_242_880, 373_297_152,
     {"_gate_up": 4, "_down": 4, "_combine_rows": 4,
      "_grouped_window_attention": 4}),
    ("lfm2", 5_242_880, 302_540_288,
     {"_gate_up": 4, "_down": 4, "_combine_rows": 4}),
    ("ling", 3_145_728, LING_TEMPS_256,
     {"_gate_up": 6, "_down": 6, "_combine_held": 6, "_delta_window": 6}),
    ("xing", 3_145_728, XING_TEMPS_256,
     {"_window_attention": 5, "_gate_up": 4, "_down": 4, "_combine_held": 4,
      **XING_STREAM_CALLS})])
def test_the_64_row_rung_compiles_beside_the_256_one(
        topo, tpu_backend, capsys, head, capacity, temps_256, in_tree):
    """The ladder's 64-row rung of each backbone's step (serve/scorer.py:
    a 64-row frame no longer runs the 256-row program), at its cell's
    size: in place on the ring, the same kernel calls as the 256-row step
    (every kernel's ``supports`` takes the quarter shape, so no fallback
    path is in play), and its code and temporaries printed. The
    temporaries are held under a quarter of the 256-row step's
    (``temps_256``: what the tests above print): the chip keeps one
    scratch region for all loaded programs, as large as the largest asks
    for, so a rung whose temporaries fit inside the 256-row step's adds
    nothing to ``bytes_reserved`` and costs its programs' code alone,
    which is why the default ladder holds the rung in every cell. Read
    here (PR 46): 211,406,336 B ``pangu``, 142,620,160 ``falconh1``,
    20,904,960 ``keye``, 56,169,472 ``lfm2``; code 12.1-19.4 MB. Since PR
    47 ``keye``'s steps hold the window kernel's grouped form once a layer
    (22,832,640 B at this rung, 373,297,152 at the 256-row one). Since PR
    51 the three steps that route through ``decoder_parts.route`` sort and
    gather nothing under it at this rung either (210,599,936 B ``pangu``,
    56,169,472 ``lfm2``, 52,811,776 ``ling``)."""
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.serve import session_state as ss

    one = SingleDeviceSharding(topo.devices[0])
    compiled = _compile_step(head, capacity, capacity + 1, one, one, batch=64)
    ring = ss.ring_size(capacity + 1, ss.default_events())
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{head} 64-row step for a described v5e: code "
              f"{mem.generated_code_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B (a quarter of the 256-row "
              f"step's: {temps_256 // 4} B)")
    assert _ring_sized(compiled, ring) == []
    assert mem.alias_size_in_bytes >= 4 * ring, mem
    assert mem.temp_size_in_bytes <= temps_256 // 4, mem
    # the in-tree kernels' calls carry `pallas_call` in their op_name;
    # XLA's grouped product is a Mosaic call of its own (`ragged-dot-none`)
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line
             and ("pallas_call" in line or "%ragged-dot-none" in line)]
    found = {}
    for line in calls:
        name = re.match(r"\s*%([\w-]+?)(\.\d+)? = ", line).group(1)
        found[name] = found.get(name, 0) + 1
    assert found == in_tree, found
    if head in ("pangu", "lfm2", "ling", "xing"):  # who routes by decoder_parts.route
        from igaming_platform_tpu.models.session_heads import HEADS

        _assert_the_router_sorts_and_gathers_nothing(
            compiled.as_text(), 64 * ss.default_events(), HEADS[head].config.top_k)


@pytest.mark.parametrize("head,sketch,sha256", [
    ("pattern", False, "3c67509ec67a17b8"), ("pattern", True, "8496bf14577e9c1b"),
    ("transformer", False, "f590c67c2d8b6af9"),
    ("transformer", True, "933ba7dbaa33d794")])
def test_the_small_heads_step_is_the_one_the_ledger_measured(head, sketch, sha256):
    """The two host-bound cells' fused step never traces the expert layer:
    its StableHLO, lowered on this sandbox's CPU at 256 rows and 5,242,880
    slots, is byte for byte what PR 34 to PR 37 read (PERF.md, section 6),
    which is how a PR that works on a backbone shows that those cells'
    device program did not move. A PR that changes that step on purpose
    writes the new prefixes here, and says so in PERF.md. PR 61 did: the
    window leaves ``session_state.windows_from_state`` as 32-bit words
    behind one fence in every session step (c51b7511f2159529,
    154ee60b366c28a6, 7037c7eb0e6f4958 and 1e8ca471c94e4fe3 until then).
    PR 67 did again: the step takes the launch's ONE packed chunk and the
    thresholds where it took seven columns, ``n`` and the thresholds as host
    arrays, and unpacks the columns behind one fence
    (``index_program.unpack_chunk``; 65dd860d54d1204b, a8840c1b072e56ac,
    177210630f848043 and 1b5d6b7b954cff6f until then)."""
    import hashlib

    capacity = 5_242_880
    text = _lower_step(head, capacity, capacity + 1, None, None,
                       sketch=sketch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == sha256


def test_admission_sync_writes_the_ring_in_place(topo):
    from jax.sharding import SingleDeviceSharding

    from igaming_platform_tpu.serve import session_state as ss

    one = SingleDeviceSharding(topo.devices[0])
    n, rows = ss.default_events(), CAPACITY + 1
    f32, i32, k = np.float32, np.int32, SYNC_SLOTS
    compiled = ss.make_ring_sync().lower(
        _spec((ss.ring_size(rows, n),), f32, one),
        _spec((rows,), i32, one), _spec((rows,), i32, one),
        _spec((k,), i32, one), _spec((k, n, ss.EVENT_WIDTH), f32, one),
        _spec((k,), i32, one), _spec((k,), i32, one)).compile()
    _assert_in_place(compiled, ss.ring_size(rows, n))


def test_slot_sharded_step_writes_each_shard_in_place(topo):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from igaming_platform_tpu.parallel.mesh import AXIS_DATA
    from igaming_platform_tpu.parallel.state_sharding import SlotShardingPlan
    from igaming_platform_tpu.serve import session_state as ss

    devices = np.array(topo.devices)
    mesh = Mesh(devices, (AXIS_DATA,))
    plan = SlotShardingPlan(mesh, devices.size)
    compiled = _compile_step(
        "pattern", SHARDED_CAPACITY, SHARDED_CAPACITY,
        NamedSharding(mesh, P(AXIS_DATA)), NamedSharding(mesh, P()),
        mesh=mesh, plan=plan)
    # memory_analysis is per device: one shard's ring.
    _assert_in_place(compiled, ss.ring_size(
        SHARDED_CAPACITY // devices.size, ss.default_events()))
    assert "all-gather" in compiled.as_text()
