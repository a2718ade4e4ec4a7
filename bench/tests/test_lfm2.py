"""What PR 41 brought to the benchmark, on the CPU: the configuration's file
against the catalog row of LFM2-8B-A1B key by key, the parameter count of its
cut, `flops_per_token` by hand, the family's contract with the harness (one
group, one stacked choice), the reference's convolution against a loop over
time, and the shape function of the two convolution calls on their event
texts."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, reducers, shapes, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAMILY = families.load("lfm2")
CELL = "lfm2_8b_a1b_l5_ep4.t8192"

_KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
          "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv",
          "conv", "conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv"]
# the config.json as the catalog row of LFM2-8B-A1B holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": _KINDS, "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts", "vocab_size"]


def _sizes(rehearse=False):
    with open(os.path.join(ROOT, "bench", "configs", "lfm2_8b_a1b_l5_ep4.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    return sizes


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_size(key):
    """Every key of the source is in the file under its own name and equal to
    it unless `reduced` lists it: the depth (the dense layers counted once,
    then one whole period), the kinds of the layers kept, the experts held
    and the vocabulary's quarter. No width is among them."""
    sizes = _sizes()
    assert sizes["reduced"] == REDUCED and set(sizes["reduced_why"]) == set(REDUCED)
    assert not [k for k in REDUCED if re.search(r"size|_dim|_rank|per_tok|heads", k)
                and k != "vocab_size"]
    if key not in REDUCED:
        assert sizes[key] == PUBLISHED[key] and type(sizes[key]) is type(PUBLISHED[key])
    elif key == "layer_types":
        assert sizes["layer_types_published"] == PUBLISHED[key]
        assert sizes[key] == [PUBLISHED[key][i] for i in sizes["layers_kept"]]
        assert sizes["layers_kept"] == [0, 2, 3, 4, 5] and len(sizes[key]) == 5
        # one whole period after the dense layer: one attention layer to three conv
        assert sorted(sizes[key][1:]) == ["conv"] * 3 + ["full_attention"]
    else:
        assert sizes[key + "_published"] == PUBLISHED[key]
        assert sizes[key] == {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
                              "vocab_size": 16384}[key]


def test_what_the_file_assumes_and_stands_for():
    sizes = _sizes()
    said = " ".join(sizes["assumed"])
    for word in ("tied head", "update rule", "2412.19437", "intermediate_size", "taps",
                 "half-split", "initialisers", "auxiliary"):
        assert word in said, word
    for word in ("rank 0 of the 4", "experts 0-7 of 32", "0-16383 of 65536", "pipeline stages"):
        assert word in sizes["stands_for"], word
    assert sizes["mesh"] == {"dp": 1} and sizes["first_expert_held"] == 0
    assert sizes["num_experts_published"] // sizes["num_experts"] == sizes["shares_each_layer"] == 4
    assert 0.9 < sizes["choice_agreement_min"] < 1 and len(sizes["choice_agreement_why"]) > 100
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["configs"][-1]
    assert entry["name"] == "lfm2_8b_a1b_l5_ep4"
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "lfm2_8b_a1b_l5_ep4", "b2_t8192", 1)
    new = ["gated_conv_share_pct", "gated_conv_fwd_roofline", "gated_conv_bwd_roofline",
           "moe_gmm1792_share_pct", "moe_gmm1792_roofline"]
    assert [m["name"] for m in bench["per_layer"][-5:]] == new
    for metric in bench["per_layer"]:
        if metric["name"] in new:
            assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s"
        elif metric["name"] in ("flash_fwd_share_pct", "flash_fwd_roofline", "flash_bwd_share_pct",
                                "flash_bwd_roofline"):
            assert metric["workloads"][-1] == CELL
        else:
            assert CELL not in metric.get("workloads", [])


def test_parameter_count_of_the_cut():
    """The table of PERF.md section 4, by hand."""
    sizes = _sizes()
    d = 2048
    conv, attn = 4 * d * d, 2 * d * d + 2 * d * 512
    dense, expert = 3 * d * 7168, 3 * d * 1792
    assert (conv, attn, dense, expert) == (16_777_216, 10_485_760, 44_040_192, 11_010_048)
    routed = d * 32 + 4 * 8 / 32 * expert  # what a token meets at even routing
    by_hand = (conv + dense) + (attn + routed) + 3 * (conv + routed) + 16384 * d
    assert FAMILY.matmul_params(sizes) == by_hand == 199_491_584
    cfg = FAMILY.build(sizes, "bfloat16")
    assert cfg.matmul_params() == by_hand
    assert (cfg.n_layer, cfg.num_dense_layers, cfg.experts_held, cfg.num_experts, cfg.top_k,
            cfg.head_dim, cfg.conv_taps) == (5, 1, 8, 32, 4, 64, 3)
    # held: the state the chip keeps (tests/test_lfm2.py counts the program's own leaves)
    held = (conv + 3 * d + dense) + (attn + 128 + d * 32 + 32 + 8 * expert) \
        + 3 * (conv + 3 * d + d * 32 + 32 + 8 * expert) + 5 * 2 * d + 16384 * d + d
    assert held == 507_820_288 and round(held * 16 / 2 ** 30, 2) == 7.57


def test_flops_per_token_at_the_cell_s_size():
    sizes = _sizes()
    by_hand = 6 * (60_817_408 + 21_561_344 + 3 * 27_852_800 + 33_554_432) + 12 * 2048 * 4096
    assert FAMILY.flops_per_token(sizes, 8192) == by_hand == 1_297_612_800
    assert FAMILY.build(sizes, "bfloat16").flops_per_token(8192) == by_hand
    # 21.3 TFLOP a step of 16,384 tokens, 108 ms at the v5e's 197 TFLOP/s
    assert round(by_hand * 16384 / 1e12, 1) == 21.3
    assert round(by_hand * 16384 / 197e12 * 1e3) == 108


def test_family_keeps_the_harness_s_contract():
    """One group and one stacked choice: the harness compiles its backward
    once for the first layer's structure and takes one "choices" entry a
    layer (bench/worker.py)."""
    sizes = _sizes(rehearse=True)
    assert FAMILY.layer_names(sizes) == ["p_0"] and families.is_routed(FAMILY)
    families.check_contract(FAMILY, sizes)
    import inspect

    source = inspect.getsource(FAMILY)
    assert "ray_tpu.ops" not in source
    assert source.count("ray_tpu.models") == 1  # `build`, the one place that names models/
    from ray_tpu.models.lfm2 import Lfm2

    cfg = FAMILY.build(sizes, "float32")
    mix = traffic.load("b2_t8192", rehearse=True)
    batch = traffic.make_batch(mix, sizes["vocab_size"], 2 ** 31 + 5, 0)
    idx = jnp.asarray(batch["idx"])
    params = Lfm2(cfg).init(jax.random.PRNGKey(0), idx)["params"]
    names, outer = families.split_params(FAMILY, params, sizes)
    assert names == ["p_0"] and sorted(outer) == ["final_norm", "tok_emb"]
    x = FAMILY.embed(outer, idx, sizes)
    own = FAMILY.choice(x, params["p_0"], sizes)
    assert own.shape == (4, *idx.shape, sizes["num_experts_per_tok"])
    y, aux = families.layer_with_aux(FAMILY, x, params["p_0"], sizes, own)
    assert y.shape == x.shape and aux == 0.0
    np.testing.assert_allclose(y, FAMILY.layer(x, params["p_0"], sizes), rtol=1e-5, atol=1e-5)
    # a choice given is used: every token to the first experts changes the result
    forced = jnp.broadcast_to(jnp.arange(sizes["num_experts_per_tok"]), own.shape)
    assert float(jnp.abs(FAMILY.layer(x, params["p_0"], sizes, choice=forced) - y).max()) > 1e-4
    # the system's one entry, in the reference's shape
    sown = Lfm2(cfg).apply({"params": params}, idx, mutable=["choices"])[1]["choices"]
    entry, = jax.tree.leaves(sown["p_0"])
    assert entry.shape == own.shape and list(sown) == ["p_0"]


def test_reference_convolution_against_a_loop_over_time():
    """`gated_conv`'s shifted slices against y_t computed one token at a time
    from the equations: w_j multiplies the input 2 - j tokens back."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, c, u = (np.asarray(jax.random.normal(k, (2, 12, 8))) for k in ks[:3])
    taps = np.asarray(jax.random.normal(ks[3], (3, 8)))
    want = np.zeros_like(b)
    for t in range(12):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += taps[j] * b[:, t - 2 + j] * u[:, t - 2 + j]
        want[:, t] *= c[:, t]
    np.testing.assert_allclose(FAMILY.gated_conv(*map(jnp.asarray, (b, c, u, taps))), want,
                               rtol=1e-5, atol=1e-6)


FWD = ("jvp_gated_conv_fwd_ custom-call -> bf16[2,8192,2048]",
       "bf16[2,8192,6144], f32[3,2048]")
BWD = ("transpose_jvp_gated_conv_bwd__ custom-call -> (bf16[2,8192,6144], f32[2,3,2048])",
       "bf16[2,8192,6144], bf16[2,8192,2048], f32[3,2048], bf16[2,8192,6144], bf16[2,8192,6144]")


def test_shape_function_against_a_hand_count():
    """The cell's two calls as the compiled step names them: b 2, T 8,192, d
    2,048, k 3 read from the shapes. Forward 8 x 2,048 bytes a token, backward
    14 x 2,048: 0.33 and 0.57 ms a layer at 819 GB/s."""
    fn = shapes.load("gated_conv")
    tokens, d, k = 2 * 8192, 2048, 3
    assert fn(*FWD) == (tokens * d * (2 * k + 1), tokens * (3 * d + d) * 2 + k * d * 4)
    assert fn(*BWD) == (tokens * d * (6 * k + 4),
                        tokens * (d + 3 * d + 3 * d) * 2 + 2 * k * d * 4)
    assert round(fn(*FWD)[1] / 819e9 * 1e3, 2) == 0.33
    assert round(fn(*BWD)[1] / 819e9 * 1e3, 2) == 0.57
    # bound by bytes at the chip's peaks, whatever the kernel's tiling
    for call in (FWD, BWD):
        flops, nbytes = fn(*call)
        assert flops / 197e12 < nbytes / 819e9
    assert fn("flash_fwd custom-call -> (bf16[32,8192,64], f32[32,1,8192])", FWD[1]) is None
    assert fn(FWD[0], "") is None  # a trace that kept no operands: nothing to read
    # the same streams handed over as three views: counted alike
    assert fn(FWD[0], "bf16[2,8192,6144], bf16[2,8192,6144], bf16[2,8192,6144], f32[3,2048]") \
        == fn(*FWD)
    assert fn(FWD[0].replace("2048]", "2048]"), "bf16[2,8192,6144], f32[4,2048]")[0] \
        == tokens * d * 9


def test_metrics_name_the_calls():
    gmm = "gmm custom-call -> bf16[24576,1792]"
    for name, matches in (("gated_conv_share_pct", (FWD[0], BWD[0])),
                          ("gated_conv_fwd_roofline", (FWD[0],)),
                          ("gated_conv_bwd_roofline", (BWD[0],)),
                          ("moe_gmm1792_share_pct", (gmm,)), ("moe_gmm1792_roofline", (gmm,))):
        spec = reducers.load_metric(name)
        hit = [t for t in (FWD[0], BWD[0], gmm, "flash_fwd custom-call -> bf16[1,2,3]")
               if re.search(spec["args"]["pattern"], t)]
        assert tuple(hit) == matches, name
    # the grouped matmul's count at this cell's buffer: 1.5 x 65,536 x 8/32 rows
    flops, _ = shapes.load("moe_gmm")(gmm, "bf16[24576,2048], bf16[8,2048,1792]")
    assert flops == 2 * 16384 * 2048 * 1792
