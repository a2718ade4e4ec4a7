"""What PR 36 brought to the benchmark, on the CPU: the configuration's file
against the catalog row of granite-4.0-h-micro key by key, the parameter
count of its cut, `flops_per_token` by hand, the reference's recurrence
against its own quadratic form, and the shape function of the two scan calls
on their recorded event texts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAMILY = families.load("granite")

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the config.json as the catalog row of granite-4.0-h-micro holds it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": _PERIOD * 4, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


def _sizes(rehearse=False):
    with open(os.path.join(ROOT, "bench", "configs", "granite4_h_micro_l10.json")) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes["rehearsal"])
    return sizes


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_keeps_every_published_size(key):
    """Every key of the source is in the file under its own name and equal
    to it unless `reduced` lists it: the depth, the list of layer kinds cut
    with it (one whole period, nine to one as published), the vocabulary's
    slice at the guide's floor."""
    sizes = _sizes()
    assert sizes["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert set(sizes["reduced_why"]) == set(sizes["reduced"])
    if key not in sizes["reduced"]:
        assert sizes[key] == PUBLISHED[key]
    elif key == "layer_types":
        assert sizes[key] == PUBLISHED[key][:10] == _PERIOD
        assert len(sizes[key]) == sizes["num_hidden_layers"]
    else:
        assert sizes[key + "_published"] == PUBLISHED[key]
        assert sizes[key] == {"num_hidden_layers": 10, "vocab_size": PUBLISHED[key] // 8}[key]


def test_what_the_file_assumes_and_stands_for():
    sizes = _sizes()
    said = " ".join(sizes["assumed"])
    for word in ("head width", "A_log", "dt_bias", "clamp", "gate before the norm", "taps",
                 "initialisers"):
        assert word in said, word
    assert "four pipeline stages" in sizes["stands_for"] and "eighth" in sizes["stands_for"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "granite4_h_micro_l10")
    assert entry["reduced"] == sizes["reduced"] and entry["source"] == sizes["source"]
    cell = next(w for w in bench["workloads"] if w["config"] == "granite4_h_micro_l10")
    assert (cell["name"], cell["traffic"], cell["chips"]) == (
        "granite4_h_micro_l10.t4096", "b1_t4096", 1)
    for name in ("ssd_share_pct", "ssd_fwd_roofline", "ssd_bwd_roofline"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [cell["name"]] and metric["moves"] == "tokens_per_s"


def test_parameter_count_of_the_cut():
    """The table of PERF.md section 4, by hand."""
    sizes = _sizes()
    w_in, w_out = 2048 * (4096 + 4352 + 64), 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    matmuls = 9 * (w_in + w_out + mlp) + (attn + mlp) + 12544 * 2048
    assert FAMILY.matmul_params(sizes) == matmuls == 771_883_008
    mamba_vectors = 4352 * 4 + 4352 + 3 * 64 + 4096
    assert FAMILY.vector_params(sizes) == 9 * mamba_vectors + 10 * 2 * 2048 + 2048 == 277_440
    assert round((matmuls + 277_440) / 1e6, 1) == 772.2
    # and the program's own count, and its parameters as it makes them
    cfg = FAMILY.build(sizes, "bfloat16")
    assert cfg.matmul_params() == matmuls and cfg.period == 10
    from ray_tpu.models.granite import Granite

    shapes_ = jax.eval_shape(lambda: Granite(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes_)) == matmuls + 277_440
    assert sorted(shapes_) == ["final_norm", "p_0", "tok_emb"] == sorted(
        FAMILY.layer_names(sizes) + ["final_norm", "tok_emb"])


def test_flops_per_token_at_the_cell_s_size():
    sizes = _sizes()
    by_hand = (6 * 771_883_008        # every matmul parameter, forward and backward
               + 6 * 1 * 4096 * 2048  # one attention layer, causal: 6 T d
               + 9 * 3 * 6 * 128 * 64 * 64)  # nine scans: 6 N P H forward, x3
    assert FAMILY.flops_per_token(sizes, 4096) == by_hand
    assert FAMILY.build(sizes, "bfloat16").flops_per_token(4096) == by_hand


def test_recurrence_equals_its_quadratic_form():
    """The reference's step-by-step scan against the whole-sequence form
    ((C B^T) * L)(Delta x), which shares nothing with it but the equations."""
    b, t, h, p, g, n = 2, 128, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0)
    a = -jnp.exp(jnp.linspace(-3.0, 1.5, h))
    bm, cm = (jax.random.normal(k, (b, t, g, n)) for k in ks[2:4])
    with jax.default_matmul_precision("highest"):
        got = FAMILY._recurrence(x, delta, a, bm, cm)
        c = jnp.cumsum(delta * a, axis=1)                       # (b, t, h)
        L = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, :, :, None],
                      jnp.exp(c[:, :, None] - c[:, None]), 0.0)  # (b, i, j, h)
        cb = jnp.repeat(jnp.einsum("bign,bjgn->bijg", cm, bm), h // g, axis=-1)
        want = jnp.einsum("bijh,bjhp->bihp", cb * L, delta[..., None] * x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


FWD = ("ssd_fwd custom-call -> (bf16[1,4096,4096], f32[1,16,4096,128])",
       "bf16[1,4096,4096], f32[1,8,4096,8], f32[1,8,4096,8], f32[1,8,8,4096], "
       "bf16[1,4096,128], bf16[1,4096,128], f32[1,4096]")
BWD = ("ssd_bwd custom-call -> (bf16[1,4096,4096], f32[1,8,4096,8], f32[1,8,4096,8], "
       "f32[1,8,8,4096], f32[1,8,4096,128], /*index=5*/f32[1,8,4096,128], f32[1,16,1,4096])",
       "bf16[1,4096,4096], bf16[1,4096,4096], f32[1,8,4096,8], f32[1,8,4096,8], "
       "f32[1,8,8,4096], bf16[1,4096,128], bf16[1,4096,128], f32[1,4096], f32[1,16,4096,128]")


def test_shape_function_on_the_recorded_calls():
    """The cell's two calls as the v5e's trace names them (my chip run, PR
    36): T 4,096, H 64, P 64, N 128, Q 256 read from the shapes."""
    ssd = shapes.load("ssd")
    q, p, n, h, chunks = 256, 64, 128, 64, 16
    forward = chunks * (h * (2 * q * q * p + 4 * q * n * p) + 2 * q * q * n)
    x, shared, delta, states = 4096 * 4096 * 2, 4096 * 128 * 2, 4096 * 64 * 4, 16 * 4096 * 128 * 4
    assert ssd(*FWD) == (forward, 2 * x + delta + 2 * shared + states)
    assert ssd(*BWD) == (2 * forward + chunks * 2 * q * q * n,
                         3 * x + 2 * delta + 4 * shared + states)
    assert forward / 4096 == pytest.approx(4.26e6, rel=1e-2)  # a token and layer
    # wrapped by the transformations it went through, found all the same
    assert ssd("transpose_jvp_ssd_bwd_ custom-call -> " + BWD[0].split("-> ")[1], BWD[1]) \
        == ssd(*BWD)
    assert ssd("flash_fwd custom-call -> (bf16[32,4096,64], f32[32,1,4096])", FWD[1]) is None
    assert ssd(FWD[0], "") is None  # a trace that kept no operands: nothing to read
    # a kernel that lays Delta and c out by another tiling is counted alike
    assert ssd(FWD[0], FWD[1].replace("f32[1,8,4096,8]", "f32[1,4096,64]").replace(
        "f32[1,8,8,4096]", "f32[1,64,4096]")) == ssd(*FWD)


def test_metrics_name_the_calls():
    import re

    from bench import reducers

    for name, matches in (("ssd_share_pct", (FWD[0], BWD[0])), ("ssd_fwd_roofline", (FWD[0],)),
                          ("ssd_bwd_roofline", (BWD[0],))):
        spec = reducers.load_metric(name)
        hit = [t for t in (FWD[0], BWD[0], "flash_fwd custom-call -> bf16[1,2,3]")
               if re.search(spec["args"]["pattern"], t)]
        assert tuple(hit) == matches
