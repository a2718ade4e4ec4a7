"""What PR 34 brought to the benchmark, on the CPU: the reference of
bench/families/keye.py (its selection against one worked row by row, in
blocks and whole, through the harness's own layer-at-a-time comparison), the
configuration's file against the published sizes, the shape functions of the
new calls, and the control's path at the rehearsal sizes."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAMILY = families.load("keye")

# the language model's config.json as the catalog row of Keye-VL-2.0-30B-A3B holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def _sizes(rehearse=True):
    with open(os.path.join(ROOT, "bench", "configs", "keye_vl2_30b_l4_ep8.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    return sizes


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_size(key):
    """Every key of the source is in the file under its own name, and equal
    to it unless `reduced` lists it: the layers, the experts held, the
    vocabulary's slice, each with its published value beside it."""
    sizes = _sizes(rehearse=False)
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    if key not in sizes["reduced"]:
        assert sizes[key] == PUBLISHED[key]
    else:
        assert sizes[key + "_published"] == PUBLISHED[key] and sizes[key] < PUBLISHED[key]
        assert key in sizes["reduced_why"]
        floor = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": PUBLISHED[key] / 8}[key]
        assert sizes[key] >= floor


def test_the_share_is_an_eighth():
    sizes = _sizes(rehearse=False)
    assert sizes["shares_each_layer"] == 8
    assert sizes["num_experts"] * 8 == sizes["num_experts_published"]
    assert sizes["vocab_size"] * 8 == sizes["vocab_size_published"]
    families.check_contract(FAMILY, sizes)
    assert 0.9 < sizes["choice_agreement_min"] < 1.0


def _layer_case(seed=0, t=128):
    sizes = _sizes()
    from ray_tpu.models.mellum import Mellum

    model = Mellum(FAMILY.build(sizes, "float32"))
    idx = jnp.asarray(np.random.default_rng(seed).integers(0, sizes["vocab_size"], (2, t)), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), idx)["params"]
    _, outer = families.split_params(FAMILY, params, sizes)
    return sizes, params, FAMILY.embed(outer, idx, sizes)


@pytest.mark.parametrize("block", [32, 64, 256])
def test_reference_selects_the_top_k_of_each_causal_row(block, monkeypatch):
    """Against a stable sort of each row of I computed here from the
    equations: min(k, t + 1) keys a query, the largest, ties to the lower
    position; in blocks of queries and whole."""
    monkeypatch.setattr(FAMILY, "QUERY_BLOCK", block)
    sizes, params, x = _layer_case()
    blk, sa = params["h_0"], sizes["sa_config"]
    seen = np.asarray(FAMILY.selected_keys(x, blk, sizes))
    with jax.default_matmul_precision("highest"):
        h = FAMILY._rms_norm(x, blk["attn_norm"]["weight"], sizes["rms_norm_eps"])
        q, k, w = FAMILY._index_operands(h, blk, sizes)
        scores = np.asarray(jnp.einsum("btj,btjs->bts", w, jax.nn.relu(
            jnp.einsum("btje,bse->btjs", q, k))))
    want = np.zeros(seen.shape, bool)
    for b in range(seen.shape[0]):
        for t in range(seen.shape[1]):
            order = np.lexsort((np.arange(t + 1), -scores[b, t, :t + 1]))
            want[b, t, order[:min(sa["topk"], t + 1)]] = True
    assert (seen.sum(-1) == np.minimum(np.arange(128) + 1, sa["topk"])).all()
    assert (seen != want).sum() <= 2  # a last bit of a score, blocks against whole


def test_reference_breaks_ties_by_position(monkeypatch):
    """An indexer whose weights are all zero scores every pair 0: each query
    keeps its first k keys."""
    sizes, params, x = _layer_case()
    blk = jax.tree.map(lambda a: a, params["h_0"])
    blk["indexer"]["ww"]["kernel"] = jnp.zeros_like(blk["indexer"]["ww"]["kernel"])
    seen = np.asarray(FAMILY.selected_keys(x, blk, sizes))
    k = sizes["sa_config"]["topk"]
    assert (seen == (np.tril(np.ones((128, 128), bool)) & (np.arange(128) < k)[None, :])).all()


def test_query_blocks_change_nothing(monkeypatch):
    sizes, params, x = _layer_case(seed=1)
    whole, _ = FAMILY.layer(x, params["h_0"], sizes)
    monkeypatch.setattr(FAMILY, "QUERY_BLOCK", 32)
    blocks, _ = FAMILY.layer(x, params["h_0"], sizes)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_reference_is_independent_of_the_program():
    with open(FAMILY.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if "import" in line and "ray_tpu" in line]
    assert imports == ["    from ray_tpu.models.mellum import INDEXED, MellumConfig"]  # in build()
    assert "bfloat16" not in text.split('"""', 2)[2].replace("compute_dtype", "")


def test_shape_functions_of_the_new_calls():
    select = shapes.load("flash_select")
    causal = shapes.flash_attention("a custom-call -> (bf16[32,16384,128], f32[32,1,16384])")
    fwd = select("flash_sel2048_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])")
    # k*t - k*k/2 scores a head of the causal call's t*t/2: 0.2344 at 2,048 of 16,384
    assert fwd[0] / causal[0] == pytest.approx((2 * 2048 * 16384 - 2048 ** 2) / 16384 ** 2)
    assert fwd[1] == causal[1]
    dq = select("transpose_jvp_flash_sel2048_bwd_dq_ custom-call -> bf16[32,16384,128]")
    dkv = select("flash_sel2048_bwd_dkv custom-call -> (bf16[32,16384,128], bf16[32,16384,128])")
    assert dq[0] * 2 == fwd[0] * 3 and dkv[0] == fwd[0] * 2
    assert select("flash_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])") is None
    assert select("flash_sel16384_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])") is None
    scores = shapes.load("index_scores")(
        "index_scores custom-call -> f32[1,16384,16384]",
        "bf16[1,16,16384,64], bf16[1,16384,64], f32[1,16384,16]")
    assert scores[0] == 2 * 16 * 64 * 16384 ** 2 // 2
    assert scores[1] == 2 * 16384 * 64 * 17 + 4 * 16384 * 16 + 4 * 16384 ** 2 // 2
    assert shapes.load("index_scores")("fusion fusion -> f32[1,16384,16384]", "") is None
    chosen = shapes.load("index_select")(
        "index_select custom-call -> s32[1,16384,512]", "f32[1,16384,16384]")
    assert chosen == (0, 4 * 16384 ** 2 // 2 + 4 * 16384 * 512)
    assert shapes.load("index_select")("gmm custom-call -> bf16[24576,768]", "") is None
    # the grouped matmuls at this cell's widths through the shape function that is there
    gmm = shapes.load("moe_gmm")("gmm custom-call -> bf16[24576,768]",
                                 "bf16[24576,2048], bf16[16,2048,768]")
    assert gmm[0] == 2 * 16384 * 2048 * 768


def test_new_metrics_read_nothing_from_a_trace_without_their_calls():
    """On the parent's program, or any cell but this one, the readers find no
    such call and the metric is left out."""
    from bench import reducers, trace

    tr = trace.Reduced({"texts": ["jit_train_step", "flash_fwd custom-call -> (bf16[8,256,64], f32[8,1,256])", ""],
                        "devices": [{"name": "/device:TPU:0", "ops": [[1, 10, 50, 2]], "async": [],
                                     "modules": [[0, 0, 100]]}], "host": [], "program": []})
    rec = {"spans": {}, "counters": {}, "step_intervals_s": [], "trace": tr,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "notes": {}}
    for name in ("flash_sel_roofline", "index_scores_roofline", "index_select_roofline"):
        assert reducers.read(name, rec) is None
    for name in ("flash_sel_share_pct", "index_share_pct"):
        assert reducers.read(name, rec) == 0.0


@pytest.mark.parametrize("script, flags", [("keye_control.py", ["--seeds", "1"]),
                                           ("keye_control.py", ["--seeds", "1", "--indexer-only"]),
                                           ("keye_keys.py", ["--seeds", "1"])])
def test_side_scripts_run_at_the_rehearsal_sizes(script, flags):
    out = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "tests", script), "--cpu", *flags],
                         capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert 0.5 < line["key_agreement"] <= 1.0
