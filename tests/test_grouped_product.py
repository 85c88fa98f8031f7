"""The served expert layer's grouped product (``ops/pallas_grouped.py``, op
``grouped_swiglu``): sorted rows through their groups' SwiGLU experts, in
chunks of rows each with its own group sizes (XLA) or by (group, row tile)
visits (the Pallas kernels, interpreted here), against a dense reference a
row in float32; and the rule that chooses the tile from static shapes, held
on the lowered text at both MoE cells' layer geometries."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import observe
from deeplearning4j_tpu.ops import pallas_grouped
from deeplearning4j_tpu.ops.pallas_grouped import (
    grouped_row_tile, grouped_swiglu_xla)
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.parallel.moe import (
    grouped_path, grouped_rows, moe_topk_share)

D, W = 16, 24
HI = jax.lax.Precision.HIGHEST


def _experts(groups, key=0, dtype=jnp.float32):
    kg, ku, kd = jax.random.split(jax.random.key(key), 3)
    return {"Wg": jax.random.normal(kg, (groups, D, W), dtype) * 0.3,
            "Wu": jax.random.normal(ku, (groups, D, W), dtype) * 0.3,
            "Wd": jax.random.normal(kd, (groups, W, D), dtype) * 0.3}


def _w(params):
    return params["Wg"], params["Wu"], params["Wd"]


def _dense(rows, params, sizes):
    """Every row through its own group's expert, one row at a time: float32
    at ``highest``. Rows past the last group give nought."""
    group = np.repeat(np.arange(len(sizes)), sizes)
    out = np.zeros(rows.shape, np.float32)
    for i, g in enumerate(group):
        r = rows[i].astype(jnp.float32)
        wg, wu, wd = (params[k][g].astype(jnp.float32)
                      for k in ("Wg", "Wu", "Wd"))
        h = jax.nn.silu(jnp.dot(r, wg, precision=HI)) * jnp.dot(
            r, wu, precision=HI)
        out[i] = np.asarray(jnp.dot(h, wd, precision=HI))
    return out


# (name, rows given, group sizes, tile)
CASES = [
    ("groups_cross_chunk_boundaries", 32, [5, 6, 7, 3, 9, 2], 8),
    ("a_group_spans_three_chunks", 32, [3, 20, 9], 8),
    ("empty_groups_between_full_ones", 32, [8, 0, 0, 11, 0, 13], 8),
    ("every_row_in_one_group", 32, [0, 0, 32, 0], 8),
    ("rows_past_the_last_group", 32, [4, 0, 5, 2], 8),
    ("no_group_holds_a_row", 16, [0, 0, 0], 8),
    ("rows_not_a_multiple_of_the_tile", 29, [6, 1, 0, 12, 7], 8),
    ("fewer_rows_than_one_tile", 13, [2, 0, 7, 3], 128),
    ("one_row_a_chunk", 6, [1, 2, 0, 3], 1),
    ("tile_equals_rows", 24, [10, 14], 24),
]


@pytest.mark.parametrize("name,n,sizes,tile", CASES,
                         ids=[c[0] for c in CASES])
def test_every_row_goes_through_its_own_group(name, n, sizes, tile):
    params = _experts(len(sizes))
    rows = jax.random.normal(jax.random.key(1), (n, D), jnp.float32)
    got = np.asarray(jax.jit(grouped_swiglu_xla, static_argnames="tile")(
        rows, *_w(params), jnp.asarray(sizes, jnp.int32), tile=tile))
    want = _dense(rows, params, sizes)
    held = sum(sizes)
    assert got.shape == (n, D) and got.dtype == np.float32
    np.testing.assert_allclose(got[:held], want[:held], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("name,n,sizes,tile", CASES[:7],
                         ids=[c[0] for c in CASES[:7]])
def test_the_chunked_form_equals_the_one_call_form(name, n, sizes, tile):
    """Chunks change which rows share a call, not a row's arithmetic: the
    held rows agree with the one-call form to float32 rounding, with the
    experts' weights in bfloat16 as they are served."""
    params = _experts(len(sizes), dtype=jnp.bfloat16)
    rows = jax.random.normal(jax.random.key(2), (n, D),
                             jnp.float32).astype(jnp.bfloat16)
    sizes_ = jnp.asarray(sizes, jnp.int32)
    one = np.asarray(grouped_swiglu_xla(rows, *_w(params), sizes_, tile=n))
    chunked = np.asarray(grouped_swiglu_xla(rows, *_w(params), sizes_,
                                            tile=tile))
    held = sum(sizes)
    np.testing.assert_allclose(chunked[:held], one[:held], rtol=1e-6,
                               atol=1e-6)


def _layer(groups=4, outputs=16, key=3):
    p = _experts(groups, key)
    p["router"] = jax.random.normal(jax.random.key(key + 1), (D, outputs),
                                    jnp.float32)
    return p


def _layer_reference(p, u, *, top_k, held, bias, valid, scale=1.0):
    """The layer's partial sum, a token and a pick at a time."""
    first, count = held
    s = jax.nn.softmax(jnp.dot(u, p["router"], precision=HI), axis=-1)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    y = np.zeros(u.shape, np.float32)
    for i in range(u.shape[0]):
        if not valid[i]:
            continue
        for e in np.asarray(chosen[i]):
            if first <= e < first + count:
                one = _dense(u[i:i + 1], {k: p[k][e - first:e - first + 1]
                                          for k in ("Wg", "Wu", "Wd")}, [1])
                y[i] += scale * float(s[i, e]) * one[0]
    return y


@pytest.mark.parametrize("tile", [8, 32, 1000])
def test_rows_not_held_or_not_valid_give_exact_nought(monkeypatch, tile):
    """Tokens that are not ``valid`` and picks of absent experts take no row
    of the grouped product: their result is exact nought whatever the chunk
    left there, and their neighbours' rows are what they are without them."""
    monkeypatch.setattr(pallas_grouped, "grouped_row_tile",
                        lambda n: min(n, tile))
    p = _layer()
    t, top_k = 23, 3
    u = jax.random.normal(jax.random.key(5), (t, D), jnp.float32)
    valid = np.arange(t) % 3 != 1
    bias = jnp.zeros((16,))
    kw = dict(top_k=top_k, n_routed=16, n_zero=0, scale=1.0, held=(2, 4),
              bias=bias)
    y, stats = moe_topk_share(p, u, valid=jnp.asarray(valid), **kw)
    want = _layer_reference(p, u, top_k=top_k, held=(2, 4), bias=bias,
                            valid=valid)
    # (jax's CPU ragged_dot multiplies at default precision: float32)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(y)[~valid].any()
    nobody = moe_topk_share(p, u, valid=jnp.zeros((t,), bool), **kw)[0]
    assert not np.asarray(nobody).any()
    assert int(stats[:4].sum()) + int(stats[-1]) == int(valid.sum()) * top_k


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["held_rows_fit_the_head", "they_do_not"])
def test_the_head_and_all_rows_give_the_same_result(monkeypatch, crowded):
    """A rank that holds 2 of 16 experts: 96 tokens x 4 picks are 384 rows,
    the head is 128 of them. The same picks through the head (or, crowded
    onto the held experts by the bias, through the conditional's all-rows
    branch) and through all rows with no conditional give the same ``y``."""
    monkeypatch.setattr(pallas_grouped, "grouped_row_tile",
                        lambda n: min(n, 32))
    t, top_k, held = 96, 4, (4, 2)
    assert grouped_rows(t, top_k, 2, 16) == (128, 384)
    p = _layer(groups=2)
    u = jax.random.normal(jax.random.key(7), (t, D), jnp.float32)
    bias = jnp.zeros((16,)).at[4:6].set(10.0 if crowded else 0.0)
    kw = dict(top_k=top_k, n_routed=16, n_zero=0, scale=1.5, held=held,
              bias=bias)
    y, stats = moe_topk_share(p, u, **kw)
    held_rows = int(stats[:2].sum())
    assert (held_rows > 128) == crowded
    monkeypatch.setattr(moe, "grouped_rows",
                        lambda t, k, *a: (t * k, t * k))       # no head
    y_all, stats_all = moe_topk_share(p, u, **kw)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats_all))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_all), rtol=1e-6,
                               atol=1e-6)
    want = _layer_reference(p, u, top_k=top_k, held=held, bias=bias,
                            valid=np.ones(t, bool), scale=1.5)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- the shape rule


def _ragged_rows(text):
    """Rows of the first operand of every ``ragged_dot`` in a lowered
    module's text."""
    return [int(m) for m in re.findall(
        r"chlo\.ragged_dot.*}> : \(tensor<(\d+)x", text)]


def _lowered_layer(t, *, top_k, routed, zero, count, d, w):
    bf16, f32 = jnp.bfloat16, jnp.float32
    sds = jax.ShapeDtypeStruct
    params = {"router": sds((d, routed + zero), bf16),
              "Wg": sds((count, d, w), bf16), "Wu": sds((count, d, w), bf16),
              "Wd": sds((count, w, d), bf16)}

    def layer(params, u, valid):
        return moe_topk_share(params, u, top_k=top_k, n_routed=routed,
                              n_zero=zero, scale=1.0, held=(0, count),
                              valid=valid)

    # lowered FOR the TPU, on the CPU: the CPU's own lowering of a grouped
    # product is a masked dense one and names no ``ragged_dot``
    return jax.jit(layer).trace(
        params, sds((t, d), f32), sds((t,), jnp.bool_)).lower(
        lowering_platforms=("tpu",)).as_text()


XING = dict(top_k=4, routed=64, zero=0, count=64, d=3584, w=1024)
LONGCAT = dict(top_k=12, routed=512, zero=256, count=16, d=6144, w=2048)


def test_xings_products_take_no_512_rows_at_once():
    """Xing4.0's cell: 128 slots x 4 picks over all 64 experts, 8 rows a
    group. No grouped product is given the 512 rows at once (XLA's pays a
    512-row tile for every expert that holds a row: PERF.md, PR 32), and the
    chunks are the body of a loop: three products a layer in the text,
    whatever the number of chunks. ``prefill``'s 2048 rows likewise."""
    for t in (128, 512):
        n = t * 4
        assert grouped_rows(t, 4, 64, 64)[0] >= n            # no head
        tile = grouped_row_tile(n)
        rows = _ragged_rows(_lowered_layer(t, **XING))
        assert rows == [tile] * 3 and tile < 512, (t, rows)


def test_longcats_head_and_all_rows_take_chunks():
    """LongCat's cell: 128 slots x 12 picks, 16 of 768 outputs held: the
    head of 128 sorted rows goes through two chunks of 64 (16 groups of 2
    rows pay for the rows a call is given too: 1.83 -> 1.60 ms a layer, my
    chip runs, PR 32), the all-rows branch of the conditional through twelve
    of 128; each is one loop with three products in its body."""
    fit, n = grouped_rows(128, 12, 16, 768)
    assert (fit, n) == (128, 1536)
    head, rest = (grouped_row_tile(r) for r in (fit, n))
    assert (head, rest) == (64, 128)
    text = _lowered_layer(128, **LONGCAT)
    assert sorted(_ragged_rows(text)) == [head] * 3 + [rest] * 3
    assert text.count("stablehlo.while") == 2


@pytest.mark.parametrize("n", [1, 7, 48, 128, 129, 512, 1536, 2048])
def test_the_tile_is_a_function_of_static_shapes(n):
    tile = grouped_row_tile(n)
    assert isinstance(tile, int) and 1 <= tile <= n
    # tiny sizes (every tier-1 model): one chunk, the one-call form
    if n <= 64:
        assert tile == n
    else:
        assert tile % 64 == 0 and -(-n // tile) <= 16


# ------------------------------------------- how it engaged, on the host


XING_PATH = dict(top_k=4, count=64, outputs=64)
LONGCAT_PATH = dict(top_k=12, count=16, outputs=768)


@pytest.mark.parametrize("held,t,shapes,path,rows", [
    ([512] * 5, 128, XING_PATH, "all", 512),           # Xing's decode step
    ([770, 760, 802, 740, 799], 512, XING_PATH, "all", 2048),   # its prefill
    ([30, 41, 28, 35], 128, LONGCAT_PATH, "head", 128),  # LongCat's decode
    ([30, 41, 129, 35], 128, LONGCAT_PATH, "all", 1536),  # one layer crowded
    ([128] * 4, 128, LONGCAT_PATH, "head", 128),          # the head, full
    ([90, 101, 88, 95], 512, LONGCAT_PATH, "head", 256),  # its prefill
])
def test_the_host_knows_the_path_from_the_counts(held, t, shapes, path, rows):
    """``moe_path``/``moe_tile`` need no read of their own: the per-expert
    counts say whether the held rows fitted the head, the shapes the rest."""
    # a layer's row: its held rows spread over the held experts, then the
    # picks to zero experts and to absent ones
    count = shapes["count"]
    stats = np.zeros((len(held), count + 2), np.int64)
    for layer, rows_held in enumerate(held):
        stats[layer, :count] = np.bincount(np.arange(rows_held) % count,
                                           minlength=count)
    stats[:, -1] = 7
    got = grouped_path(stats, t, top_k=shapes["top_k"],
                       outputs=shapes["outputs"])
    assert got == (path, grouped_row_tile(rows))


def test_note_moe_counts_how_the_products_engaged():
    observe.reset()
    stats = np.asarray([[3, 1, 0, 2], [2, 2, 1, 1]])       # 2 held + zero + absent
    span = observe.tracer().span("serving_decode", category="serving")
    for _ in range(3):
        out = observe.note_moe(stats, span, decode_step=True,
                               grouped=("head", 128))
    observe.note_moe(stats, None, grouped=("all", 64))
    assert out["moe_path"] == "head" and out["moe_tile"] == 128
    assert span.args["moe_path"] == "head" and span.args["moe_tile"] == 128
    snap = observe.metrics().snapshot()
    assert snap['dl4j_tpu_moe_grouped_steps_total{path="head",tile="128"}'][
        "value"] == 3
    assert snap['dl4j_tpu_moe_grouped_steps_total{path="all",tile="64"}'][
        "value"] == 1
    # without it (a caller that does not say): the four older arguments only
    assert "moe_path" not in observe.note_moe(stats)


# ------------------------------------------------ the Pallas kernels


def _bf16_experts(groups, d, w, key=11):
    ks = jax.random.split(jax.random.key(key), 3)
    mk = lambda k, sh: (jax.random.normal(k, sh, jnp.float32)  # noqa: E731
                        * 0.1).astype(jnp.bfloat16)
    return (mk(ks[0], (groups, d, w)), mk(ks[1], (groups, d, w)),
            mk(ks[2], (groups, w, d)))


# (name, rows given, group sizes, row tile): 128 x 256 experts
PALLAS_CASES = [
    ("groups_cross_tile_edges", 64, [5, 6, 17, 3, 9, 12], 16),
    ("a_group_spans_three_tiles", 64, [3, 40, 9], 16),
    ("empty_groups_between_full_ones", 64, [16, 0, 0, 11, 0, 13], 16),
    ("every_row_in_one_group", 64, [0, 0, 64, 0], 32),
    ("rows_past_the_last_group", 64, [4, 0, 5, 2], 16),
    ("no_group_holds_a_row", 32, [0, 0, 0], 16),
    ("one_tile", 32, [10, 14, 1], 32),
]


@pytest.mark.parametrize("name,n,sizes,tm", PALLAS_CASES,
                         ids=[c[0] for c in PALLAS_CASES])
def test_the_kernels_agree_with_the_chunked_form(monkeypatch, name, n, sizes,
                                                 tm):
    """The Pallas grouped SwiGLU (interpreted) against the XLA path and the
    dense reference: every held row by its own expert, whatever the tile and
    however the groups lie across its edges."""
    d, w = 128, 256
    monkeypatch.setattr(pallas_grouped, "grouped_row_tile", lambda n: tm)
    # blocks of 128 columns: two column blocks a product, each visit twice
    monkeypatch.setattr(pallas_grouped, "_BLOCK_BYTES", 256 * 128 * 2)
    wg, wu, wd = _bf16_experts(len(sizes), d, w)
    rows = jax.random.normal(jax.random.key(12), (n, d),
                             jnp.float32).astype(jnp.bfloat16)
    sizes_ = jnp.asarray(sizes, jnp.int32)
    held = sum(sizes)
    got = np.asarray(jax.jit(pallas_grouped.grouped_swiglu_pallas)(
        rows, wg, wu, wd, sizes_))
    xla = np.asarray(grouped_swiglu_xla(rows, wg, wu, wd, sizes_, tile=16))
    assert got.shape == (n, d) and got.dtype == np.float32
    # float32 sums in another order, and a hidden value that rounds to the
    # other bfloat16 neighbour now and then
    np.testing.assert_allclose(got[:held], xla[:held], rtol=2e-2, atol=2e-2)
    want = _dense(rows, {"Wg": wg, "Wu": wu, "Wd": wd}, sizes)
    np.testing.assert_allclose(got[:held], want[:held], rtol=3e-2, atol=3e-2)


def test_a_weight_block_holds_the_whole_contraction():
    """At both cells' widths a block (k, tn) fits the budget with few column
    blocks a product: a group visited twice in a row is fetched once."""
    width = pallas_grouped._block_width
    assert width(3584, 1024) == 1024 and width(1024, 3584) == 3584
    assert width(6144, 2048) == 1024 and width(2048, 6144) == 3072
    assert width(32, 24) == 0                       # no whole lane


def test_the_registry_takes_the_kernels_where_they_fit():
    from deeplearning4j_tpu.ops import registry, validation

    usable = registry().get("grouped_swiglu").platform_usable["tpu"]
    sds = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16

    def args(n, count, d, w, dt=bf16):
        return (sds((n, d), dt), sds((count, d, w), dt), sds((count, d, w), dt),
                sds((count, w, d), dt), sds((count,), jnp.int32))

    assert usable(*args(512, 64, 3584, 1024))        # Xing's decode
    assert usable(*args(2048, 64, 3584, 1024))       # its prefill
    assert usable(*args(128, 16, 6144, 2048))        # LongCat's head
    assert usable(*args(1536, 16, 6144, 2048))
    assert not usable(*args(12, 16, 32, 24))         # the tiny models
    assert not usable(*args(512, 64, 3584, 1024, jnp.float32))
    assert not usable(*args(500, 64, 3584, 1024))    # rows fill no tile
    (check,) = validation.cases()["grouped_swiglu"]
    check()
