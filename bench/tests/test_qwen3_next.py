"""What PR 64 brought to the benchmark, on the CPU: the configuration's file
against the catalog row of Qwen3-Next-80B-A3B-Instruct key by key, the
parameter count of its cut and `flops_per_token` by hand, the family's
contract with the harness (one group, one stacked choice), the reference's
delta rule against the recurrence worked a head and a step at a time in
numpy, its partial rotary and gated attention against a loop over queries,
what each control changes, and the shape function on this cell's event
texts. Every entry of BENCHMARK.json is found by its name."""

import inspect
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, reducers, shapes, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAMILY = families.load("qwen3_next")
CONFIG, CELL = "qwen3_next_80b_l5_ep32", "qwen3_next_80b_l5_ep32.t8192"

# the config.json as the catalog row of Qwen3-Next-80B-A3B-Instruct holds it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CUT = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 18992}
NEW = ["gdn_share_pct", "gdn_fwd_roofline", "gdn_bwd_roofline", "moe_gmm512_share_pct"]
# the causal flash pair and the head norm's pair run here too: the cell is appended to these
APPENDED = ["flash_fwd_share_pct", "flash_fwd_roofline", "flash_bwd_share_pct",
            "flash_bwd_roofline", "kda_norm_share_pct"]


def _sizes(rehearse=False, **changed):
    with open(os.path.join(ROOT, "bench", "configs", f"{CONFIG}.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    sizes.update(changed)
    return sizes


def test_the_row_is_the_catalog_s():
    """Where the guide's catalog is on this box, PUBLISHED is its row's
    `config` key for key; elsewhere the file is held to PUBLISHED alone."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog on this box")
    with open(path) as f:
        row, = (r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert row["config"] == PUBLISHED and row["source_url"] == _sizes()["source"]


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_size(key):
    """Every key of the source is in the file under its own name and equal to
    it unless `reduced` lists it: the depth, the experts held and the
    vocabulary's eighth. No width is among them."""
    sizes = _sizes()
    assert sizes["reduced"] == REDUCED and set(sizes["reduced_why"]) == set(REDUCED)
    if key not in REDUCED:
        assert not re.search(r"^(num_hidden_layers|num_experts|vocab_size)$", key)
        assert sizes[key] == PUBLISHED[key] and type(sizes[key]) is type(PUBLISHED[key])
    else:
        assert sizes[key + "_published"] == PUBLISHED[key] and sizes[key] == CUT[key]


def test_what_the_file_assumes_and_stands_for():
    sizes = _sizes()
    said = " ".join(sizes["assumed"])
    for word in ("no bias, silu after it", "one leaf", "1e-6", "128^-1/2", "not doubled",
                 "A_log = log(u)", "uniform in (0, 16)", "dt_bias ones", "forget within a few steps",
                 "exp of something positive", "norm comes before the gate", "starting at one",
                 "halves turned", "first partial_rotary_factor x head_dim = 64", "rope_scaling null",
                 "softmax over all 512", "one row of d", "no multi-token prediction head",
                 "auxiliary", "initialisers"):
        assert word in said, word
    for word in ("rank 0 of the 32", "experts 0-15 of 512", "0-18991 of 151936", "four of the 32",
                 "pipeline stages", "shared expert", "layers 0-4"):
        assert word in sizes["stands_for"], word
    for word in ("512,591,104", "7.64 GiB", "88.25 M", "81.80 M", "38.90 M"):
        assert word in sizes["reduced_why"]["num_hidden_layers"], word
    assert "3.75 GiB" in sizes["reduced_why"]["num_experts"]  # why 32 held is no cut
    assert sizes["mesh"] == {"dp": 1} and sizes["first_expert_held"] == 0
    assert sizes["layers_kept"] == [0, 1, 2, 3, 4]
    assert FAMILY._kinds(sizes) == ["linear", "linear", "linear", "full", "linear"]
    assert sizes["num_experts_published"] // sizes["num_experts"] == sizes["shares_each_layer"] == 32
    assert sizes["vocab_size_published"] // sizes["vocab_size"] == 8
    assert (sizes["compute_dtype"], sizes["param_dtype"]) == ("bfloat16", "float32")
    assert 0.9 < sizes["choice_agreement_min"] < 1 and len(sizes["choice_agreement_why"]) > 100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    cell, = (w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "b2_t8192", 1)
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable() and text.isascii()
    # by name, never by position or count, and nothing of who else a shared metric lists
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert [m for m in NEW + APPENDED if m not in listed] == []
    for name in NEW:
        metric = listed[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s"
        assert metric["layer"] == "kernel" and metric["unit"] == "%"
        assert metric["better"] == ("higher" if name.endswith("roofline") else "lower")
    for name in APPENDED:
        assert CELL in listed[name]["workloads"]
    # no grouped-matmul roofline in this cell (PERF.md section 7)
    assert not [m["name"] for m in bench["per_layer"]
                if "gmm" in m["name"] and m["name"].endswith("roofline")
                and CELL in m.get("workloads", [])]


def test_parameter_count_of_the_cut():
    """The count of ISSUE 64 and PERF.md section 4, by hand."""
    sizes = _sizes()
    d = 2048
    gdn = d * (2 * 2048 + 2 * 4096) + d * 64 + 4096 * d  # the matmuls' part
    gdn_leaves = gdn + 4 * 8192 + 32 + 32 + 128  # filters, A_log, dt_bias, o_norm
    attn = d * 8192 + 2 * d * 512 + 4096 * d
    expert, router = 3 * d * 512, d * 512
    assert (gdn, gdn_leaves, attn, expert, router) == (
        33_685_504, 33_718_464, 27_262_976, 3_145_728, 1_048_576)
    norms = 2 * d
    routed = router + 16 * expert + expert + d  # 16 held, the shared expert and its gate's row
    linear_block = gdn_leaves + norms + routed
    full_block = attn + 2 * 256 + norms + routed
    outer = 2 * 18_992 * d + d
    assert (routed, linear_block, full_block, outer) == (
        54_528_000, 88_250_560, 81_795_584, 77_793_280)
    held = 4 * linear_block + full_block + outer
    assert held == 512_591_104 and round(held * 16 / 2 ** 30, 2) == 7.64
    assert 5 * 16 * expert == 251_658_240 and round(5 * 16 * expert * 16 / 2 ** 30, 2) == 3.75
    by_hand = 4 * gdn + attn + 5 * (router + expert + d + 10 * 16 / 512 * expert) + 18_992 * d
    assert FAMILY.matmul_params(sizes) == by_hand == 226_797_568
    cfg = FAMILY.build(sizes, "bfloat16")
    assert cfg.matmul_params() == by_hand
    assert (cfg.gdn_params(), cfg.attention_params()) == (gdn, attn)
    assert (cfg.n_layer, cfg.experts_held, cfg.num_experts, cfg.top_k, cfg.gdn_key_heads,
            cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_conv, cfg.n_head,
            cfg.n_kv_head, cfg.head_dim, cfg.rotary_dim, cfg.shared_dim, cfg.expert_dim) == (
        5, 16, 512, 10, 16, 32, 128, 128, 4, 16, 2, 256, 64, 512, 512)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention", "linear_attention")
    assert (cfg.rope_theta, cfg.rms_eps) == (1e7, 1e-6)


def test_flops_per_token_at_the_cell_s_size():
    """The delta rule by the recurrence: a token's three products with a
    value head's (128, 128) state, forward and twice that backward."""
    sizes = _sizes()
    scores = 3 * 8192 * 16 * 512
    delta = 3 * 3 * 2 * 32 * 128 * 128
    assert (scores, delta) == (201_326_592, 9_437_184)
    by_hand = 6 * 226_797_568 + scores + 4 * delta
    assert FAMILY.flops_per_token(sizes, 8192) == by_hand == 1_599_860_736
    assert FAMILY.build(sizes, "bfloat16").flops_per_token(8192) == by_hand
    assert FAMILY.flops_per_token(sizes, 4096) == by_hand - scores // 2
    # 26.2 TFLOP a step of 16,384 tokens, 133 ms at the v5e's 197 TFLOP/s; the DeltaNet
    # mixers 53% of it, the gated attention 23%, the head 15%
    assert round(by_hand * 16384 / 1e12, 1) == 26.2
    assert round(by_hand * 16384 / 197e12 * 1e3) == 133
    assert round(4 * (6 * 33_685_504 + delta) / by_hand, 3) == 0.529
    assert round((6 * 27_262_976 + scores) / by_hand, 3) == 0.228
    assert round(6 * 18_992 * 2048 / by_hand, 3) == 0.146


def test_family_keeps_the_harness_s_contract():
    """One group and one stacked choice, nothing of the program outside
    `build`, and a file this family does not implement refused."""
    sizes = _sizes(rehearse=True)
    assert FAMILY.layer_names(sizes) == ["p_0"] and families.is_routed(FAMILY)
    families.check_contract(FAMILY, sizes)
    source = inspect.getsource(FAMILY)
    assert "ray_tpu.ops" not in source
    assert source.count("ray_tpu.models") == 1  # `build`, the one place that names models/
    from ray_tpu.models.qwen3_next import Qwen3Next

    cfg = FAMILY.build(sizes, "float32")
    mix = traffic.load("b2_t8192", rehearse=True)
    batch = traffic.make_batch(mix, sizes["vocab_size"], 2 ** 31 + 5, 0)
    idx = jnp.asarray(batch["idx"])
    params = Qwen3Next(cfg).init(jax.random.PRNGKey(0), idx)["params"]
    names, outer = families.split_params(FAMILY, params, sizes)
    assert names == ["p_0"] and sorted(outer) == ["final_norm", "lm_head", "tok_emb"]
    x = FAMILY.embed(outer, idx, sizes)
    own = FAMILY.choice(x, params["p_0"], sizes)
    assert own.shape == (5, *idx.shape, sizes["num_experts_per_tok"])
    y, aux = families.layer_with_aux(FAMILY, x, params["p_0"], sizes, own)
    assert y.shape == x.shape and aux == 0.0
    np.testing.assert_allclose(y, FAMILY.layer(x, params["p_0"], sizes), rtol=1e-5, atol=1e-5)
    forced = jnp.broadcast_to(jnp.arange(sizes["num_experts_per_tok"]), own.shape)
    assert float(jnp.abs(FAMILY.layer(x, params["p_0"], sizes, choice=forced) - y).max()) > 1e-4
    sown = Qwen3Next(cfg).apply({"params": params}, idx, mutable=["choices"])[1]["choices"]
    entry, = jax.tree.leaves(sown["p_0"])
    assert entry.shape == own.shape and list(sown) == ["p_0"]


@pytest.mark.parametrize("other", [
    {"rope_scaling": {"type": "yarn"}}, {"norm_topk_prob": False}, {"decoder_sparse_step": 2},
    {"mlp_only_layers": [0]}, {"tie_word_embeddings": True}, {"use_sliding_window": True},
    {"layers_kept": [1, 2, 3, 4, 5]}, {"partial_rotary_factor": 0.3}])
def test_a_file_the_family_does_not_implement_is_refused(other):
    with pytest.raises(ValueError):
        FAMILY.build({**_sizes(rehearse=True), **other}, "float32")


@pytest.mark.parametrize("rep", [1, 2])
def test_reference_delta_rule_against_a_loop_over_steps(rep):
    """`_recurrence` against the equation worked a value head and a step at a
    time in numpy float64: the state decayed by the step's one number, the
    delta term taken on the decayed state, the rank-one gain, the output read
    from the new state and scaled by K^-1/2, value head j on key head j //
    rep; T no multiple of the checkpointed block."""
    b, t, hk, kd, vd = 1, 70, 2, 8, 4
    hv = hk * rep
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k = (jax.random.normal(key, (b, t, hk, kd)) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, t, hv, vd))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(FAMILY._recurrence(q, k, v, g, beta))
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    want = np.zeros((b, t, hv, vd))
    for head in range(hv):
        S = np.zeros((kd, vd))
        for step in range(t):
            k_t, alpha = k[0, step, head // rep], np.exp(g[0, step, head])
            S = alpha * (np.eye(kd) - beta[0, step, head] * np.outer(k_t, k_t)) @ S \
                + beta[0, step, head] * np.outer(k_t, v[0, step, head])
            want[0, step, head] = S.T @ q[0, step, head // rep] / math.sqrt(kd)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_reference_rotary_turns_a_head_s_first_entries_alone():
    """`_rotary` of 8 of 32: the last 24 as they were, the first 8 a rotation
    of their halves by position x theta^(-2i/8) (numpy, a position and a pair
    at a time), position 0 left as it is."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 6, 2, 32))
    got = np.asarray(FAMILY._rotary(x, 8, 1e7))
    x = np.asarray(x, np.float64)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:].astype(np.float32))
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    for pos in range(6):
        for i in range(4):
            ang = pos * 1e7 ** (-2 * i / 8)
            a, b = x[0, pos, :, i], x[0, pos, :, i + 4]
            np.testing.assert_allclose(got[0, pos, :, i], a * np.cos(ang) - b * np.sin(ang),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(got[0, pos, :, i + 4], a * np.sin(ang) + b * np.cos(ang),
                                       rtol=1e-4, atol=1e-5)


def test_reference_gated_attention_against_a_loop_over_queries():
    """`_gated_attention` against numpy: q and k normed a head, the partial
    rotary, head h on key-value head h // 2, scores over sqrt(D), the sigmoid
    gate on the heads' output before W_o."""
    sizes = _sizes(rehearse=True)
    H, G, D, d, T = 4, 2, 32, sizes["hidden_size"], 9
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    a = {"wq": {"kernel": jax.random.normal(ks[0], (d, H * D)) / 8},
         "wg": {"kernel": jax.random.normal(ks[1], (d, H * D)) / 8},
         "wk": {"kernel": jax.random.normal(ks[2], (d, G * D)) / 8},
         "wv": {"kernel": jax.random.normal(ks[3], (d, G * D)) / 8},
         "wo": {"kernel": jax.random.normal(ks[4], (H * D, d)) / 8},
         "q_norm": {"weight": 1 + 0.3 * jax.random.normal(ks[5], (D,))},
         "k_norm": {"weight": 1 + 0.3 * jax.random.normal(ks[6], (D,))}}
    h = jax.random.normal(ks[7], (1, T, d))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(FAMILY._gated_attention(h, a, sizes))
        turn = lambda u: np.asarray(FAMILY._rotary(jnp.asarray(u, jnp.float32)[None], 8, 1e7)[0],
                                    np.float64)
    p = jax.tree.map(lambda x: np.asarray(x, np.float64), a)
    x = np.asarray(h[0], np.float64)
    norm = lambda u, w: u / np.sqrt((u ** 2).mean(-1, keepdims=True) + 1e-6) * w
    q = turn(norm((x @ p["wq"]["kernel"]).reshape(T, H, D), p["q_norm"]["weight"]))
    k = turn(norm((x @ p["wk"]["kernel"]).reshape(T, G, D), p["k_norm"]["weight"]))
    v = (x @ p["wv"]["kernel"]).reshape(T, G, D)
    out = np.zeros((T, H, D))
    for head in range(H):
        for t in range(T):
            s = k[:t + 1, head // 2] @ q[t, head] / math.sqrt(D)
            w = np.exp(s - s.max())
            out[t, head] = (w / w.sum()) @ v[:t + 1, head // 2]
    gate = 1 / (1 + np.exp(-(x @ p["wg"]["kernel"])))
    np.testing.assert_allclose(got[0], (out.reshape(T, -1) * gate) @ p["wo"]["kernel"],
                               rtol=1e-4, atol=1e-5)


HOOKS = [("OPERAND", lambda u: jax.lax.reduce_precision(u, exponent_bits=4, mantissa_bits=3)),
         ("RESET_EVERY", 64), ("NO_DELTA_TERM", True), ("DECAY_AFTER", True), ("KEY_HEAD_J", True),
         ("ROTARY_ALL", True)]


@pytest.mark.parametrize("hook,value", HOOKS, ids=[h for h, _ in HOOKS])
def test_the_controls_change_what_they_say(hook, value, monkeypatch):
    """bench/tests/qwen3_next_control.py's hooks: `OPERAND` rounds every
    matmul's operands, `RESET_EVERY` drops the carried state, `NO_DELTA_TERM`
    leaves beta k k^T S out, `DECAY_AFTER` applies the decay after the
    update, `KEY_HEAD_J` reads key head j (mod 16) for value head j,
    `ROTARY_ALL` turns all of a head; with none set the reference is what it
    was."""
    sizes = _sizes(rehearse=True)
    from ray_tpu.models.qwen3_next import Qwen3Next

    idx = jnp.asarray(traffic.make_batch(traffic.load("b2_t8192", rehearse=True),
                                         sizes["vocab_size"], 2 ** 31 + 6, 0)["idx"])
    params = Qwen3Next(FAMILY.build(sizes, "float32")).init(jax.random.PRNGKey(1), idx)["params"]
    x = FAMILY.embed({"tok_emb": params["tok_emb"]}, idx, sizes)
    held = FAMILY.choice(x, params["p_0"], sizes)
    sound = FAMILY.layer(x, params["p_0"], sizes, held)
    with monkeypatch.context() as patch:
        patch.setattr(FAMILY, hook, value)
        gap = float(jnp.abs(FAMILY.layer(x, params["p_0"], sizes, held) - sound).max())
    assert gap > 1e-4, (hook, gap)
    assert FAMILY.OPERAND is None and FAMILY.RESET_EVERY is None
    assert not any((FAMILY.NO_DELTA_TERM, FAMILY.DECAY_AFTER, FAMILY.KEY_HEAD_J, FAMILY.ROTARY_ALL))
    np.testing.assert_array_equal(FAMILY.layer(x, params["p_0"], sizes, held), sound)


GDN_OPERANDS = "bf16[2,8192,2048], bf16[2,8192,2048], bf16[2,8192,4096], f32[2,16,128,4,64]"
GDN_FWD = ("jvp_gdn_fwd_ custom-call -> (bf16[2,8192,4096], f32[2,128,4096,128], "
           "f32[2,4096,128])", GDN_OPERANDS)
GDN_BWD = ("transpose_jvp_gdn_bwd_ custom-call -> (bf16[2,8192,2048], bf16[2,8192,2048], "
           "bf16[2,8192,4096], f32[2,16,128,4,64])",
           GDN_OPERANDS + ", bf16[2,8192,4096], f32[2,128,4096,128]")
KDA_FWD = ("jvp_kda_fwd_ custom-call -> (bf16[2,8192,4096], f32[2,128,4096,128], "
           "f32[2,4096,128])",
           "bf16[2,8192,4096], bf16[2,8192,4096], bf16[2,8192,4096], bf16[2,8192,4096], "
           "f32[1,4096], f32[1,4096], f32[2,32,8192,1]")
GMM = ("gmm custom-call -> bf16[10240,512]", "bf16[10240,2048], bf16[16,2048,512]")
TGMM = ("tgmm custom-call -> f32[16,2048,512]", "bf16[2048,10240], bf16[10240,512]")


def test_the_shape_function_counts_this_cell_s_calls():
    """The cell's calls as the compiled step names them: 2 x 128 chunks of 64
    steps, 32 value heads of (128, 128) on 16 key heads: a chunk's products
    inside it 2 C C K a value head, the solve by substitution C C (K + V),
    the three products with the state 2 C K V each and A_qk Vn C C V; the
    backward two forwards and the inside made again: KDA's count at the same
    heads, chunk and tokens. Each operand and result moved once, q and k
    half of KDA's (a key head's, once), the decays 1/128 of KDA's, the chunk
    states (537 MB) the same."""
    gdn = shapes.load("gdn")
    c, k, v, chunks_heads = 64, 128, 128, 2 * 128 * 32
    inside = 2 * c * c * k + c * c * (k + v)
    forward = chunks_heads * (inside + 6 * c * k * v + c * c * v)
    keys, values = 2 * 8192 * 2048 * 2, 2 * 8192 * 4096 * 2
    rates, states = 2 * 2 * 8192 * 32 * 4, 2 * 128 * 4096 * 128 * 4
    assert gdn(*GDN_FWD) == (forward, 2 * keys + 2 * values + rates + states)
    assert gdn(*GDN_BWD) == (2 * forward + chunks_heads * inside,
                             4 * keys + 3 * values + 2 * rates + states)
    assert forward == 73_014_444_032 and states == 536_870_912
    assert gdn(*GDN_FWD)[0] == shapes.load("kda")(*KDA_FWD)[0]
    assert gdn(*KDA_FWD) is None and shapes.load("kda")(*GDN_FWD) is None
    assert gdn("fusion -> bf16[2,8192,4096]", "bf16[2,8192,4096]") is None
    # one value head a key head: the same call at 32 key heads counts the same operations
    # and q and k's bytes twice
    one = tuple(part.replace("bf16[2,8192,2048], bf16[2,8192,2048]",
                             "bf16[2,8192,4096], bf16[2,8192,4096]")
                .replace("f32[2,16,128,4,64]", "f32[2,32,128,2,64]") for part in GDN_FWD)
    assert gdn(*one) == (forward, 4 * keys + 2 * values + rates + states)
    chunked = gdn(*GDN_FWD)[0] + gdn(*GDN_BWD)[0]
    assert chunked / (9_437_184 * 16384) == pytest.approx(1.53, abs=0.01)


def test_metrics_name_the_calls():
    texts = (GDN_FWD[0], GDN_BWD[0], KDA_FWD[0],
             "checkpoint_jvp_gdn_fwd_ custom-call -> (bf16[2,8192,4096])", GMM[0], TGMM[0],
             "jvp_kda_norm_fwd custom-call -> bf16[2,8192,4096]",
             "jvp_flash_fwd_ custom-call -> (bf16[32,8192,256], f32[32,1,8192])")
    for name, matches in (("gdn_share_pct", (texts[0], texts[1], texts[3])),
                          ("gdn_fwd_roofline", (texts[0], texts[3])),
                          ("gdn_bwd_roofline", texts[1:2]),
                          ("moe_gmm512_share_pct", texts[4:6]),
                          ("kda_share_pct", texts[2:3]), ("kda_norm_share_pct", texts[6:7]),
                          ("flash_fwd_share_pct", texts[7:8])):
        spec = reducers.load_metric(name)
        hit = tuple(t for t in texts if re.search(spec["args"]["pattern"], t))
        assert hit == tuple(matches), name
    assert [reducers.load_metric(n)["args"]["shape_function"] for n in NEW[1:3]] == ["gdn", "gdn"]
    assert reducers.load_metric("gdn_share_pct")["reducer"] == "ops_share_of_busy"
    spec, other = (reducers.load_metric(n) for n in ("moe_gmm512_share_pct",
                                                     "moe_gmm1024_share_pct"))
    assert (spec["args"]["pattern"], spec["reducer"]) == (other["args"]["pattern"], other["reducer"])
    # every other metric that reads this cell's trace leaves the two new names alone
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]
                 if CELL in m.get("workloads", [CELL])]
    for name in names:
        try:
            pattern = reducers.load_metric(name)["args"].get("pattern")
        except Exception:
            continue
        if pattern and not name.startswith("gdn_"):
            assert not [t for t in (texts[0], texts[1]) if re.search(pattern, t)], name
