"""The main path's Pallas kernels, compiled by the installed TPU compiler.

Interpret mode (all the rest of the suite) never legalises for Mosaic, so a
kernel can pass every CPU test and still be refused by the chip's compiler.
Each case here lowers one kernel with ``interpret=False`` at the width
``chip_smoke.py`` runs it (BERT-base b16 x s512) for a DESCRIBED ``v5e:2x2``
device — no chip attached, nothing executes — and asserts the Mosaic call is
in the compiled text. The paged decode kernel is compiled inside the serving
engine's own ``decode`` program, at the benchmark cell's geometry, together
with the other programs that take the KV pool: what is held there is that
none of them copies the pool.

The topology is described inside the module-scoped fixture only: one
process at a time may load the TPU library, and pytest-xdist imports this
file in every worker, so nothing here touches ``topologies`` at import.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nn.updater import UPDATERS
from deeplearning4j_tpu.ops.pallas_attention import flash_attention
from deeplearning4j_tpu.ops.pallas_layernorm import fused_layer_norm_pallas
from deeplearning4j_tpu.ops.pallas_matmul import fused_matmul_bias_act_pallas
from deeplearning4j_tpu.ops.pallas_updater import fused_updater_helper
from deeplearning4j_tpu.ops.quantized import matmul_int8_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises without libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for an unattached chip can be written to the
    # persistent cache but never read back: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_mosaic(text):
    assert "tpu_custom_call" in text


# The serving cell's geometry (benchmarks/traffic/chat-closed.json): GPT-2
# small, 32 slots x 65 pages of 16, prompts bucketed to 896.
SLOTS, PAGE, PAGES_PER_SEQ, MAX_PROMPT = 32, 16, 65, 896
N_PAGES = SLOTS * PAGES_PER_SEQ


# what one test compiled, for the next one that reads the same programs
_COMPILED: dict = {}


def _pool_programs(one_chip, monkeypatch, dtype):
    """The engine's own ``decode`` and ``write_prompt`` (and the cache's
    ``copy_page``) compiled from shapes alone: neither the pool (2.45 GB in
    float32) nor the weights exist on this host. Dispatch is steered to its
    TPU branch here, in the test: the process still sees the CPU. Returns
    the pool, the programs that take it, and ``prefill`` lowered (whoever
    reads it compiles it)."""
    import importlib

    cached = ("gpt", jnp.dtype(dtype).name)
    if cached in _COMPILED:
        return _COMPILED[cached]

    from deeplearning4j_tpu.models.gpt import (
        GptConfig, gpt_programs, init_gpt_params)
    from deeplearning4j_tpu.ops import tuning
    from deeplearning4j_tpu.serving.cache import PagedKVCache
    from deeplearning4j_tpu.serving.engine import (
        build_decode, build_prefill, build_write)

    registry = importlib.import_module("deeplearning4j_tpu.ops.registry")
    monkeypatch.setattr(registry, "current_platform", lambda: "tpu")
    monkeypatch.setattr(tuning, "current_device_kind", lambda: "tpu_v5_lite")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cfg = GptConfig.base()
    i32, f32 = jnp.int32, jnp.float32
    pool = sds((cfg.layers, 2, N_PAGES + 1, PAGE, cfg.hidden), dtype)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_gpt_params(jax.random.key(0), cfg,
                                               dtype)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    slot = lambda dt: sds((SLOTS,), dt)  # noqa: E731
    programs = {
        "decode": build_decode(gpt_programs(cfg).decode_step, PAGE,
                               N_PAGES).lower(
            params, pool, sds((SLOTS, PAGES_PER_SEQ), i32), slot(i32),
            slot(i32), slot(i32), key, slot(f32), slot(i32), slot(f32)),
        "write_prompt": build_write(PAGE, N_PAGES).lower(
            pool, sds((cfg.layers, 2, MAX_PROMPT, cfg.hidden), dtype),
            sds((PAGES_PER_SEQ,), i32), sds((), i32)),
        "copy_page": PagedKVCache._build_copy(None).lower(
            pool, sds((), i32), sds((), i32)),
    }
    prefill = build_prefill(gpt_programs(cfg).prefill).lower(
        params, sds((1, MAX_PROMPT), i32), sds((), i32), key, sds((1,), f32),
        sds((1,), i32), sds((1,), f32), slot(i32), sds((), i32))
    _COMPILED[cached] = (pool, {k: v.compile() for k, v in programs.items()},
                         prefill)
    return _COMPILED[cached]


def _assert_pool_in_place(pool, compiled, n_pages, temp_share=0.1):
    """Every instruction whose result has the pool's element count is the
    pool's parameter, an in-place scatter or dynamic-update-slice (or the
    fusion around one), in the pool's row-major layout; nothing has one
    layer's ``[pages + 1, page, ...]`` shape; the program's temporaries stay
    under ``temp_share`` (a tenth) of the pool."""
    n_pool = math.prod(pool.shape)
    pool_bytes = n_pool * jnp.dtype(pool.dtype).itemsize
    in_place = ("parameter", "scatter", "dynamic-update-slice", "fusion",
                "get-tuple-element", "tuple", "bitcast")
    result = re.compile(
        r"^\s*(?:ROOT )?%[\w.\-]+ = (\(?)\w+\[([\d,]*)\](\{[^ ]*)? "
        r"([\w\-]+)\(")
    for name, c in compiled.items():
        for line in c.as_text().splitlines():
            m = result.match(line)
            if not m or m.group(1):
                continue
            dims = [int(d) for d in m.group(2).split(",") if d]
            assert dims[:2] != [n_pages + 1, PAGE], (name, line[:200])
            if math.prod(dims) != n_pool:
                continue
            assert m.group(4) in in_place, (name, line[:200])
            # row-major: the minor-to-major list counts down to 0
            row_major = "{" + ",".join(
                str(i) for i in reversed(range(len(dims)))) + ":"
            assert (m.group(3) or "").startswith(row_major), (name,
                                                              line[:200])
        temp = c.memory_analysis().temp_size_in_bytes
        assert temp < pool_bytes * temp_share, (name, temp)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_serving_programs_update_the_pool_in_place(one_chip, monkeypatch,
                                                   dtype):
    """No program that takes the KV pool copies it, re-lays it out or slices
    a layer out of it (PERF.md, PR 26: two such copies in ``decode``, two in
    ``write_prompt`` and twelve layer slices were 49 of a 70 ms decode step).
    Held on the compiled text (``_assert_pool_in_place``)."""
    pool, compiled, _prefill = _pool_programs(one_chip, monkeypatch, dtype)
    _assert_pool_in_place(pool, compiled, N_PAGES)
    decode = compiled["decode"].as_text()
    assert decode.count('custom_call_target="tpu_custom_call"') == 12
    assert decode.count("scatter(") >= 24


# The latent cell's geometry (benchmarks/traffic/reason-closed.json):
# LongCat-Flash at its published widths, 4 layers, 16 held experts, 128
# slots x 96 pages of 16, prompts bucketed to 512.
LC_SLOTS, LC_PAGES_PER_SEQ, LC_MAX_PROMPT = 128, 96, 512
LC_N_PAGES = LC_SLOTS * LC_PAGES_PER_SEQ


def _latent_models():
    """The two served latent-attention models at their cells' sizes: what
    makes the configuration, its parameters, its cache row and its programs,
    and the cache row the cell has."""
    from deeplearning4j_tpu.models import longcat, xing

    return {
        "latent": (longcat.LongcatConfig(num_layers=4, vocab_size=16384,
                                         held_experts=(0, 16)),
                   longcat.init_longcat_params, longcat.longcat_cache_rows,
                   longcat.longcat_programs, (8, 1, 640)),
        "xing": (xing.XingConfig(num_hidden_layers=6,
                                 first_k_dense_replace=1),
                 xing.init_xing_params, xing.xing_cache_rows,
                 xing.xing_programs, (6, 1, 640)),
    }


def _latent_programs(one_chip, monkeypatch, which="latent"):
    """A latent-attention model's ``decode``, ``write_prompt`` and
    ``prefill`` from shapes alone (LongCat-Flash: 10.35 GB of weights and a
    2.0 GB latent pool; Xing4.0: 9.58 GB and 1.5 GB, which this host never
    makes), as :func:`_pool_programs` returns GPT-2's."""
    import importlib

    if which in _COMPILED:
        return _COMPILED[which]

    from deeplearning4j_tpu.ops import tuning
    from deeplearning4j_tpu.serving.engine import (
        build_decode, build_prefill, build_write)

    registry = importlib.import_module("deeplearning4j_tpu.ops.registry")
    monkeypatch.setattr(registry, "current_platform", lambda: "tpu")
    monkeypatch.setattr(tuning, "current_device_kind", lambda: "tpu_v5_lite")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cfg, init_params, cache_rows, programs, want_rows = _latent_models()[which]
    rows = cache_rows(cfg)
    assert rows == want_rows
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    pool = sds((rows.layers, rows.sides, LC_N_PAGES + 1, PAGE, rows.width),
               bf16)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(jax.random.key(0), cfg, bf16)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    slot = lambda dt: sds((LC_SLOTS,), dt)  # noqa: E731
    compiled = {
        "decode": build_decode(programs(cfg).decode_step, PAGE,
                               LC_N_PAGES).lower(
            params, pool, sds((LC_SLOTS, LC_PAGES_PER_SEQ), i32), slot(i32),
            slot(i32), slot(i32), key, slot(f32), slot(i32),
            slot(f32)).compile(),
        "write_prompt": build_write(PAGE, LC_N_PAGES).lower(
            pool, sds((rows.layers, rows.sides, LC_MAX_PROMPT, rows.width),
                      bf16),
            sds((LC_PAGES_PER_SEQ,), i32), sds((), i32)).compile(),
    }
    prefill = build_prefill(programs(cfg).prefill).lower(
        params, sds((1, LC_MAX_PROMPT), i32), sds((), i32), key,
        sds((1,), f32), sds((1,), i32), sds((1,), f32), slot(i32),
        sds((), i32))
    _COMPILED[which] = (pool, compiled, prefill)
    return _COMPILED[which]


def test_latent_serving_programs_update_the_pool_in_place(one_chip,
                                                          monkeypatch):
    """LongCat-Flash's ``decode`` and ``write_prompt``: the pool of 640-lane
    rows (512 latent | 64 rotary key | 64 dead) stays row-major and is
    updated in place, nothing pool-sized is made, the latent kernel is there
    once an attention sub-layer under its own name, and the programs fit the
    chip beside the weights."""
    pool, compiled, _prefill = _latent_programs(one_chip, monkeypatch)
    _assert_pool_in_place(pool, compiled, LC_N_PAGES)
    decode = compiled["decode"].as_text()
    assert len(re.findall(r"%latent_decode_attention[.\d]* = .*"
                          r'custom_call_target="tpu_custom_call"',
                          decode)) == pool.shape[0]
    mem = compiled["decode"].memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.7e9


def test_xing_serving_programs_update_the_pool_in_place(one_chip,
                                                        monkeypatch):
    """Xing4.0's ``decode`` and ``write_prompt`` at its cell's geometry (one
    dense and five expert layers with all 64 experts, the whole vocabulary,
    128 slots): the same 640-lane row and pool as LongCat's, row-major and
    updated in place; the latent kernel is on its Pallas path at 32 heads,
    once a layer under its own name; the four streams and the all-rows
    grouped products fit the chip beside 9.58 GB of weights; ``prefill``
    compiles too."""
    pool, compiled, prefill = _latent_programs(one_chip, monkeypatch, "xing")
    # decode's 173 MB of temporaries against a 1.51 GB pool: two sets of
    # float32 logits over 131072 words are 134 MB of them
    _assert_pool_in_place(pool, compiled, LC_N_PAGES, temp_share=0.15)
    decode = compiled["decode"].as_text()
    kernels = re.findall(r"%latent_decode_attention[.\d]* = (\S+) .*"
                         r'custom_call_target="tpu_custom_call"', decode)
    assert len(kernels) == pool.shape[0] == 6
    assert all(k.startswith("bf16[128,32,512]") for k in kernels), kernels
    mem = compiled["decode"].memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.7e9
    mem = prefill.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.7e9


# Brumby's cell (benchmarks/traffic/reason-closed-32.json): five layers at
# the published widths, the whole vocabulary, 32 slots, prompts bucketed to
# 512.
BR_SLOTS, BR_MAX_PROMPT = 32, 512


def _state_programs(one_chip, monkeypatch):
    """Brumby's ``decode``, state ``write_prompt`` and ``prefill`` from
    shapes alone (6.41 GB of weights and a 5.5 GB pool of slot states, which
    this host never makes). Returns (pool shapes, compiled, prefill)."""
    import importlib

    if "brumby" in _COMPILED:
        return _COMPILED["brumby"]

    from deeplearning4j_tpu.models import brumby
    from deeplearning4j_tpu.ops import tuning
    from deeplearning4j_tpu.serving.engine import (
        build_prefill, build_state_decode, build_state_write)

    registry = importlib.import_module("deeplearning4j_tpu.ops.registry")
    monkeypatch.setattr(registry, "current_platform", lambda: "tpu")
    monkeypatch.setattr(tuning, "current_device_kind", lambda: "tpu_v5_lite")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    cfg = brumby.BrumbyConfig(num_hidden_layers=5)
    geo = brumby.brumby_slot_state(cfg).arrays
    assert geo == {"S": ((5, 8, 65, 128, 128), "float32"),
                   "z": ((5, 8, 65, 128), "float32")}
    i32, f32, bf16 = jnp.int32, jnp.float32, jnp.bfloat16
    pool = {n: sds((BR_SLOTS,) + shape, dt) for n, (shape, dt) in geo.items()}
    state = {n: sds(shape, dt) for n, (shape, dt) in geo.items()}
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: brumby.init_brumby_params(
            jax.random.key(0), cfg, bf16)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    slot = lambda dt: sds((BR_SLOTS,), dt)  # noqa: E731
    programs = brumby.brumby_programs(cfg)
    compiled = {
        "decode": build_state_decode(programs.decode_step).lower(
            params, pool, slot(i32), slot(i32), slot(i32), key, slot(f32),
            slot(i32), slot(f32)).compile(),
        "write_prompt": build_state_write().lower(
            pool, state, sds((), i32)).compile(),
    }
    prefill = build_prefill(programs.prefill).lower(
        params, sds((1, BR_MAX_PROMPT), i32), sds((), i32), key,
        sds((1,), f32), sds((1,), i32), sds((1,), f32), slot(i32),
        sds((), i32))
    _COMPILED["brumby"] = (pool, compiled, prefill)
    return _COMPILED["brumby"]


def test_brumby_serving_programs_update_the_state_pool_in_place(one_chip,
                                                                monkeypatch):
    """Brumby's ``decode`` and state ``write_prompt`` at its cell's geometry
    (five layers, 40 heads over 8, the whole vocabulary, 32 slots of 171.7 MB
    of float32 state): both alias the whole pool input to output and make
    nothing pool-sized (a second copy is 5.5 GB: out of memory beside 6.41
    GB of weights); the retention kernel is on its Pallas path once a layer
    under its own name over the whole pool; ``prefill`` compiles and fits
    beside the pool too."""
    pool, compiled, prefill = _state_programs(one_chip, monkeypatch)
    pool_bytes = sum(math.prod(a.shape) * 4 for a in pool.values())
    assert pool_bytes == 32 * 5 * 8 * 65 * 129 * 128 * 4
    n_state = math.prod(pool["S"].shape)
    result = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                        r"([\w\-]+)\(")
    in_place = ("parameter", "dynamic-update-slice", "fusion",
                "get-tuple-element", "bitcast")
    for name, c in compiled.items():
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes, (name, mem)
        assert mem.temp_size_in_bytes < 0.1 * pool_bytes, (name, mem)
        for line in c.as_text().splitlines():
            m = result.match(line)
            if m and math.prod(int(d) for d in m.group(1).split(",")
                               if d) == n_state:
                assert m.group(2) in in_place, (name, line[:200])
    decode = compiled["decode"].as_text()
    kernels = re.findall(r"%retention_decode[.\d]* = \((\S+) .*"
                         r'custom_call_target="tpu_custom_call".*', decode)
    assert len(kernels) == 5
    assert all(k.startswith("f32[32,5,8,65,128,128]") for k in kernels)
    mem = compiled["decode"].memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.7e9
    mem = prefill.compile().memory_analysis()
    # the pool is not prefill's argument, but it is on the chip meanwhile
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes + pool_bytes) < 15.7e9


def test_the_grouped_products_are_the_kernels_at_both_cells(one_chip,
                                                           monkeypatch):
    """The expert layers' grouped products in the compiled ``decode`` and
    ``prefill`` of both latent models are the registry's Pallas kernels
    under their own names, a gate-and-up and a down call an expert layer and
    branch (LongCat's conditional holds the head's and all rows'), over the
    rows the shape rule says; XLA's ``ragged_dot``, which pays a 512-row
    tile for every expert that holds a row, is in neither (PERF.md, PR 32:
    15 such calls were 22 of a 31 ms decode program)."""
    for which, rows in (("xing", {"decode": [512] * 5, "prefill": [2048] * 5}),
                        ("latent", {"decode": [128, 1536] * 4,
                                    "prefill": [256, 6144] * 4})):
        _pool, compiled, prefill = _latent_programs(one_chip, monkeypatch,
                                                    which)
        for name, text in (("decode", compiled["decode"].as_text()),
                           ("prefill", prefill.compile().as_text())):
            assert "ragged-dot" not in text, (which, name)
            for kernel, dt in (("grouped_gate_up", "bf16"),
                               ("grouped_down", "f32")):
                got = re.findall(
                    rf"%{kernel}[.\d]* = {dt}\[(\d+),\d+\]\S* custom-call\(.*"
                    r'custom_call_target="tpu_custom_call"', text)
                assert sorted(map(int, got)) == sorted(rows[name]), (
                    which, name, kernel, got)


def _unconditional(text):
    """The lines of a compiled module that run whenever the program does:
    the entry computation and every computation reached from it other than
    as a ``conditional``'s branch."""
    bodies, name, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(2)
            bodies[name] = []
            if head.group(1):
                entry = name
        elif name is not None:
            bodies[name].append(line)
    called = re.compile(r"\b(\w+)=\{?(%[\w.\-]+(?:, *%[\w.\-]+)*)\}?")
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in bodies[comp]:
            for how, names in called.findall(line):
                if how not in ("branch_computations", "true_computation",
                               "false_computation"):
                    todo += [n.strip(" %") for n in names.split(",")
                             if n.strip(" %") in bodies]
    return [line for comp in seen for line in bodies[comp]]


@pytest.mark.parametrize("variant,vocab", [("gpt", 50257),
                                           ("latent", 16384)])
def test_the_samplers_sorts_stand_inside_a_conditional(one_chip, monkeypatch,
                                                       variant, vocab):
    """``decode`` and ``prefill`` sort the vocabulary (top-k, top-p) only in
    a branch of the sampler's conditional, which greedy traffic does not
    take: two such sorts, run whatever the knobs, were 3.7 of the 7.9 ms of
    GPT-2's decode program and the largest thing the device did (PERF.md,
    PR 28)."""
    if variant == "gpt":
        _pool, compiled, prefill = _pool_programs(one_chip, monkeypatch,
                                                  jnp.float32)
    else:
        _pool, compiled, prefill = _latent_programs(one_chip, monkeypatch)
    vocab_sort = re.compile(r"= \(?\w+\[\d+,%d\]\S* .*\bsort\(" % vocab)
    for name, program in (("decode", compiled["decode"]),
                          ("prefill", prefill.compile())):
        text = program.as_text()
        # the filter body's two, wherever they stand
        assert len([ln for ln in text.splitlines()
                    if vocab_sort.search(ln)]) == 2, name
        always = _unconditional(text)
        for ln in always:
            assert not vocab_sort.search(ln), (name, ln[:200])
        assert any(" conditional(" in ln for ln in always), name


@pytest.mark.parametrize("bh,t,causal,rate", [
    (8, 4096, True, 0.0),      # the flash_min_t crossover shape
    (192, 512, False, 0.1),    # BERT-base b16 x 12 heads, in-kernel dropout
])
def test_flash_attention_fwd_and_grad(one_chip, bh, t, causal, rate):
    def loss(q, k, v, seed):
        out = flash_attention(q, k, v, None, seed if rate else None, None,
                              causal, None, None, False, rate)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((bh, t, 64), jnp.bfloat16)
    text = _compiled_text(
        one_chip, jax.value_and_grad(loss, argnums=(0, 1, 2)),
        qkv, qkv, qkv, ((1, 1), jnp.int32))
    _assert_mosaic(text)


@pytest.mark.parametrize("rows", [8192, 16])
def test_fused_matmul_bias_gelu(one_chip, rows):
    text = _compiled_text(
        one_chip,
        functools.partial(fused_matmul_bias_act_pallas, activation="gelu",
                          interpret=False),
        ((rows, 768), jnp.bfloat16), ((768, 3072), jnp.bfloat16),
        ((3072,), jnp.bfloat16))
    _assert_mosaic(text)


@pytest.mark.parametrize("lead,activation", [((16, 512), "gelu"),
                                             ((16, 512), "none"),
                                             ((16,), "none")])
def test_fused_layer_norm(one_chip, lead, activation):
    text = _compiled_text(
        one_chip,
        functools.partial(fused_layer_norm_pallas, activation=activation,
                          interpret=False),
        (lead + (768,), jnp.bfloat16), ((768,), jnp.bfloat16),
        ((768,), jnp.bfloat16))
    _assert_mosaic(text)


@pytest.mark.parametrize("kind", sorted(UPDATERS))
def test_fused_updater_every_kind(one_chip, kind):
    """All eleven kinds share one kernel body; the Adam family traced
    ``beta**t`` (``math.powf``, which Mosaic cannot legalise) inside it
    until the per-step scalars moved out to ``Updater.scalars``."""
    leaf = ((768, 3072), jnp.float32)
    n_state = len(UPDATERS[kind]().init_state(jnp.zeros((), jnp.float32)))
    text = _compiled_text(
        one_chip,
        functools.partial(fused_updater_helper, kind=kind, interpret=False),
        leaf, leaf, ((), jnp.float32), ((), jnp.int32), *([leaf] * n_state))
    _assert_mosaic(text)


def test_fused_updater_bf16_embedding_leaf(one_chip):
    """BERT-base's largest leaf (word embeddings) as bf16 Adam trains it:
    30522 x 768 does not divide into (rows, 128) blocks, so the pad path
    and the bf16 store casts are both in the kernel."""
    leaf = ((30522, 768), jnp.bfloat16)
    text = _compiled_text(
        one_chip,
        functools.partial(fused_updater_helper, kind="Adam",
                          interpret=False),
        leaf, leaf, ((), jnp.float32), ((), jnp.int32), leaf, leaf)
    _assert_mosaic(text)


@pytest.mark.parametrize("rows", [8192, 32])
def test_matmul_int8(one_chip, rows):
    text = _compiled_text(
        one_chip, functools.partial(matmul_int8_pallas, interpret=False),
        ((rows, 768), jnp.bfloat16), ((768, 3072), jnp.int8),
        ((3072,), jnp.float32))
    _assert_mosaic(text)
