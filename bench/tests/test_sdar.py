"""What PR 61 brought to the benchmark, on the CPU: the configuration's file
against the published sizes, the reference of bench/families/sdar.py (its
mask against the four lines worked pair by pair, its noise and its loss by
hand, in blocks and whole, through the harness's own layer-at-a-time
comparison), the shape function of the new calls, the entries BENCHMARK.json
gained, found by name, and the control's path at the rehearsal sizes."""

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
FAMILY = families.load("sdar")
CELL = "sdar_30b_a3b_l5_ep8.t8192"

# config.json as the catalog row of SDAR-30B-A3B-Chat holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def _sizes(rehearse=True):
    with open(os.path.join(ROOT, "bench", "configs", "sdar_30b_a3b_l5_ep8.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    return sizes


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_size(key):
    """Every key of the source is in the file under its own name, and equal
    to it unless `reduced` lists it: the layers, the experts held, the
    vocabulary's slice, each with its published value beside it and at or
    over its floor."""
    sizes = _sizes(rehearse=False)
    assert sizes["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    if key not in sizes["reduced"]:
        assert sizes[key] == PUBLISHED[key]
    else:
        assert sizes[key + "_published"] == PUBLISHED[key] and sizes[key] < PUBLISHED[key]
        assert key in sizes["reduced_why"]
        floor = {"num_hidden_layers": 4, "num_experts": 8, "vocab_size": PUBLISHED[key] / 8}[key]
        assert sizes[key] >= floor


def test_what_the_file_assumes_and_stands_for():
    sizes = _sizes(rehearse=False)
    assert sizes["source"].startswith("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat")
    assert sizes["shares_each_layer"] == 8 and sizes["num_hidden_layers"] == 5
    assert sizes["num_experts"] * 8 == sizes["num_experts_published"]
    assert sizes["vocab_size"] * 8 == sizes["vocab_size_published"]
    assert sizes["mask_token_id"] == sizes["vocab_size"] - 1 and sizes["block_length"] == 4
    assumed = " ".join(sizes["assumed"])
    for said in ("block_length 4", "mask_token_id", "noise_eps", "CE_i / t_blk(i)",
                 "block 0 of every sequence is left clean", "fold_in", "jax.random.split",
                 "RMSNorm over each head"):
        assert said in assumed, said
    assert "ep=8" in sizes["stands_for"].replace(" ", "") or "8 chips" in sizes["stands_for"]
    families.check_contract(FAMILY, sizes)
    assert 0.9 < sizes["choice_agreement_min"] < 1.0 and sizes["choice_agreement_why"]
    assert set(sizes["rehearsal"]) <= set(sizes)


def test_benchmark_gained_one_configuration_one_cell_and_three_metrics():
    bench = _benchmark()
    config = next(c for c in bench["configs"] if c["name"] == "sdar_30b_a3b_l5_ep8")
    assert config["file"] == "bench/configs/sdar_30b_a3b_l5_ep8.json"
    assert config["reduced"] == _sizes(rehearse=False)["reduced"]
    assert config["source"] == _sizes(rehearse=False)["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar_30b_a3b_l5_ep8", "b1_t8192", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("flash_bd_share_pct", "flash_bd_fwd_roofline", "flash_bd_bwd_roofline"):
        assert metrics[name]["workloads"] == [CELL] and metrics[name]["unit"] == "%"
        assert metrics[name]["layer"] == "kernel" and metrics[name]["moves"] == "tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "bench", "layer_metrics", name + ".json"))
    # the accepted roofline counts the buffer's rows / 1.5 (bench/shape_functions/moe_gmm.py),
    # which is the room this family's buffers have: ops/moe.py's own
    for name in ("moe_gmm768_share_pct", "moe_gmm768_roofline", "qk_prep_share_pct"):
        assert CELL in metrics[name]["workloads"]
    from ray_tpu.ops import moe

    assert shapes.load("moe_gmm").__globals__["ROW_HEADROOM"] == 1.5
    assert moe.buffer_rows(16384, 8, 16, 128) == 1.5 * 16384
    with open(os.path.join(ROOT, "bench", "traffic", "b1_t8192.json")) as f:
        mix = json.load(f)
    assert (mix["generator"], mix["batch"], mix["seq_len"], mix["reference_rows"]) == \
        ("uniform_packed", 1, 8192, 1)


@pytest.mark.parametrize("name", ["flash_share_pct", "flash_roofline"])
def test_the_dense_cells_metric_does_not_list_the_cell(name):
    """Its pattern matches every pallas call by design and lists the dense
    cells alone; the new calls' own metrics read them."""
    metric = next(m for m in _benchmark()["per_layer"] if m["name"] == name)
    assert CELL not in metric["workloads"]


@pytest.mark.parametrize("t,length", [(16, 4), (32, 8), (24, 4)])
def test_reference_mask_is_its_four_lines(t, length):
    """`seen` over every pair of a small stream against the lines worked one
    pair at a time in Python."""
    half = t // 2
    at = jnp.arange(t)
    got = np.asarray(FAMILY.seen((at >= half)[:, None], (at % half)[:, None],
                                 (at >= half)[None, :], (at % half)[None, :], length))
    for q in range(t):
        for k in range(t):
            i, j = q % half, k % half
            if q < half and k < half:
                want = j // length == i // length
            elif q < half:
                want = j // length < i // length
            elif k >= half:
                want = j // length <= i // length
            else:
                want = False
            assert got[q, k] == want, (q, k)
    # every query sees something, a noised one its whole block and nothing after it
    assert got.any(1).all() and (got[:half, :half].sum(1) <= length).all()


def _case(seed=0, t=128, rows=1):
    sizes = _sizes()
    from ray_tpu.models.sdar import SDAR

    model = SDAR(FAMILY.build(sizes, "float32"))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], (rows, t + 1)), jnp.int32)
    idx, targets = tokens[:, :-1], tokens[:, 1:]
    params = model.init(jax.random.PRNGKey(seed), idx)["params"]
    return sizes, model, params, idx, targets


def test_reference_embeds_the_stream_of_step_0_s_noise():
    sizes, _, params, idx, _ = _case()
    _, outer = families.split_params(FAMILY, params, sizes)
    x = FAMILY.embed(outer, idx, sizes)
    table = outer["tok_emb"]["embedding"]
    masked, level = FAMILY._noise(sizes, *idx.shape)
    assert x.shape == (1, 256, sizes["hidden_size"]) and x.dtype == jnp.float32
    np.testing.assert_array_equal(x[:, 128:], table[idx])
    np.testing.assert_array_equal(x[:, :128], table[jnp.where(masked, sizes["mask_token_id"], idx)])
    assert not masked[:, :4].any() and 20 < int(masked.sum()) < 110
    assert float(level.min()) >= sizes["noise_eps"] and float(level.max()) < 1.0


def test_reference_loss_reads_the_noised_half_at_its_own_positions():
    """By hand: the clean tokens from the shifted targets, the masked
    positions' cross-entropy over their block's level, over B (T - L)."""
    sizes, _, params, idx, targets = _case(seed=1)
    _, outer = families.split_params(FAMILY, params, sizes)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 256, sizes["hidden_size"]))
    got = float(FAMILY.head_loss(outer, x, targets, sizes))
    masked, level = (np.asarray(a) for a in FAMILY._noise(sizes, 1, 128))
    with jax.default_matmul_precision("highest"):
        h = FAMILY._rms_norm(x[:, :128], outer["final_norm"]["weight"], sizes["rms_norm_eps"])
        logp = np.asarray(jax.nn.log_softmax(h @ outer["lm_head"], axis=-1))
    want = sum(-logp[0, i, int(idx[0, i])] / level[0, i] for i in range(128) if masked[0, i]) / 124
    assert got == pytest.approx(want, rel=1e-5)
    # the clean half's activations are none of the loss's
    other = x.at[:, 128:].set(0.0)
    assert float(FAMILY.head_loss(outer, other, targets, sizes)) == pytest.approx(got, rel=1e-6)


def test_query_blocks_change_nothing(monkeypatch):
    sizes, _, params, idx, _ = _case(seed=1)
    _, outer = families.split_params(FAMILY, params, sizes)
    x = FAMILY.embed(outer, idx, sizes)
    whole, _ = FAMILY.layer(x, params["h_0"], sizes)
    monkeypatch.setattr(FAMILY, "QUERY_BLOCK", 32)
    blocks, _ = FAMILY.layer(x, params["h_0"], sizes)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_harness_compares_the_first_step_with_the_reference():
    """bench/worker.py's own comparison (a layer at a time, choices held from
    the model's forward at its default step 0) against the first step of a
    fresh TrainStep, in float32 at the rehearsal sizes: the harness's two
    judged numbers within their limits by orders."""
    from bench import traffic, worker
    from bench.run import TOLERANCE
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    sizes = _sizes()
    mix = traffic.load("b1_t8192", rehearse=True)
    ts = TrainStep(FAMILY.build(sizes, "float32"),
                   make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    state = ts.init(jax.random.PRNGKey(7))
    first = traffic.make_batch(mix, sizes["vocab_size"], 7, 0)
    ref = worker._reference_check(FAMILY, sizes, mix, ts, state["params"], first)
    state, m = ts.step(state, ts.shard_batch(first))
    rel = worker._compared({"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"])}, ref)
    assert rel["rel_diff"]["loss"] < TOLERANCE["loss"] / 10
    assert rel["rel_diff"]["grad_norm"] < TOLERANCE["grad_norm"] / 10
    assert rel["choice_agreement"] > 0.999
    # the second step's noise is another: the same batch, another loss
    _, again = ts.step(state, ts.shard_batch(first))
    assert abs(float(again["loss"]) - float(m["loss"])) > 1e-3


def test_reference_is_independent_of_the_program():
    with open(FAMILY.__file__) as f:
        text = f.read()
    imports = [line for line in text.splitlines() if "import" in line and "ray_tpu" in line]
    assert imports == ["    from ray_tpu.models.sdar import SDARConfig"]  # in build()
    assert "bfloat16" not in text.split('"""', 2)[2].replace("compute_dtype", "")


def test_flops_and_parameters_count_the_doubled_stream():
    sizes = _sizes(rehearse=False)
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 8 * 16 // 128 * 3 * 2048 * 768
    assert FAMILY.matmul_params(sizes) == 2 * 5 * layer + 18992 * 2048
    flops = FAMILY.flops_per_token(sizes, 8192)
    assert flops == 6 * FAMILY.matmul_params(sizes) + 5 * 12 * 4096 * 8196
    assert FAMILY.layer_names(sizes) == ["h_0", "h_1", "h_2", "h_3", "h_4"]


def test_shape_function_of_the_new_calls():
    blockdiff = shapes.load("flash_blockdiff")
    fwd = blockdiff("flash_bd4_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])")
    pairs = 8192 * 8192 + 8192 * 4
    assert fwd == (2 * 2 * pairs * 128 * 32, 32 * (4 * 16384 * 128 * 2 + 16384 * 4))
    bwd = blockdiff("transpose_jvp_flash_bd4_bwd_fused_ custom-call -> "
                    "(bf16[32,16384,128], bf16[32,16384,128], bf16[32,16384,128])")
    assert bwd == (5 * 2 * pairs * 128 * 32, 32 * (8 * 16384 * 128 * 2 + 16384 * 4))
    # a quarter of the stream's pairs, and 8 more a query for the blocks
    causal = shapes.flash_attention("a custom-call -> (bf16[32,16384,128], f32[32,1,16384])")
    assert fwd[0] / causal[0] == pytest.approx(0.5 * (1 + 4 / 8192))
    wide = blockdiff("flash_bd16_fwd custom-call -> (bf16[8,2048,64], f32[8,1,2048])")
    assert wide[0] == 2 * 2 * (1024 * 1024 + 1024 * 16) * 64 * 8
    for other in ("flash_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])",
                  "flash_win1024_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])",
                  "flash_bd4_fwd custom-call -> (bf16[32,16384,128], bf16[32,16384,128])",
                  "gmm custom-call -> bf16[24576,768]"):
        assert blockdiff(other) is None


def test_new_metrics_read_the_pair_and_nothing_else():
    """From a trace that holds the pair the three metrics read it; from the
    parent's program, or any cell but this one, the readers find no such call
    and leave the rooflines out."""
    from bench import reducers, trace

    def rec(texts):
        tr = trace.Reduced({"texts": ["jit_train_step", *texts, ""],
                            "devices": [{"name": "/device:TPU:0",
                                         "ops": [[n + 1, 10 + 100 * n, 50, 2 + n] for n in range(len(texts))],
                                         "async": [], "modules": [[0, 0, 1000]]}],
                            "host": [], "program": []})
        return {"spans": {}, "counters": {}, "step_intervals_s": [], "trace": tr,
                "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "notes": {}}

    ours = rec(["flash_bd4_fwd custom-call -> (bf16[32,16384,128], f32[32,1,16384])",
                "transpose_jvp_flash_bd4_bwd_fused_ custom-call -> (bf16[32,16384,128], "
                "bf16[32,16384,128], bf16[32,16384,128])",
                "fusion fusion -> bf16[16384,2048]"])
    assert reducers.read("flash_bd_share_pct", ours) == pytest.approx(100 * 2 / 3)
    assert reducers.read("flash_bd_fwd_roofline", ours) > 0
    assert reducers.read("flash_bd_bwd_roofline", ours) > reducers.read("flash_bd_fwd_roofline", ours)
    theirs = rec(["flash_fwd custom-call -> (bf16[8,256,64], f32[8,1,256])"])
    assert reducers.read("flash_bd_share_pct", theirs) == 0.0
    for name in ("flash_bd_fwd_roofline", "flash_bd_bwd_roofline"):
        assert reducers.read(name, theirs) is None


@pytest.mark.parametrize("flags", [["--faults", "fp8,bf16"],
                                   ["--faults", "clean_copy,causal_within", "--mask"]])
def test_control_runs_at_the_rehearsal_sizes(flags):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "tests", "sdar_control.py"), "--cpu",
         "--seeds", "1", *flags], capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
    by_fault = {line["fault"]: line for line in lines if "fault" in line}
    if "--mask" in flags:
        added = lines[0]["attention_added_to_the_noised_half_against_the_sound"]
        # the system's layer is the sound mask's within bf16; a wrong mask is not
        assert added["system"]["l2"] < 0.02 < min(added["clean_copy"]["l2"], added["causal_within"]["l2"])
    else:
        assert by_fault["bf16"]["would_pass"] and not by_fault["fp8"]["would_pass"]
