"""models/remat.py: which residuals a block's remat saves, as a pure function
of the step's shapes and the chips' bytes_limit. Over nine of the benchmark's
cells and over a limit swept downward: the names are the first rung and
rungs of the family's own, never fewer than the first rung, never worth
more as the limit falls; what the rule reckons is held to what the chip's
allocator read; a shape it has never seen gets fewer names, not a total
over the limit; and every process of a mesh reckons with the same limit.
"""

import importlib
import json
import os
import types

import jax
import numpy as np
import pytest

from bench import families
from ray_tpu.models import remat
from ray_tpu.parallel.mesh import batch_sharding, make_mesh, stream_sharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = remat.GIB
# `bytes_limit` of a TPU v5e chip, as device.memory_stats() gave it in the
# chip runs of PR 33 (15.748 GiB; 16,909,334,528 in one of them).
V5E_LIMIT = 16909336064
ATTN = ("attn_q", "attn_k", "attn_v")
MLP = ("mlp_up",)
GATE_UP, OUT = ("moe_gate", "moe_up"), ("moe_out",)  # ops/moe.py:KEPT_PRODUCTS
UP_OUT = ("moe_up", "moe_out")  # ops/moe.py:RELU2.products
CONV, LATENT, SHARED = ("conv_bcu", "conv_y"), ATTN + ("attn_q_shared", "attn_k_shared"), ("shared_up",)
GATE = ("attn_gate",)  # models/layers.py:LlamaAttention's gate projection (models/afmoe.py)
SSCAN = ("sscan_y", "sscan_states")  # ops/selective_scan.py's output and chunk states
# cell: configuration, (B, T) of its traffic, and the names the rule takes
# on a v5e after the first rung (the four routed cells' since PR 45, which
# named the expert layer's products and fitted the `block` term again)
CELLS = {
    "gpt2_small.t256": ("gpt2_small", (128, 256), ATTN + MLP),
    "gpt2_small.t1024": ("gpt2_small", (32, 1024), ATTN + MLP),
    "mistral_7b_l8.fsdp4_t8192": ("mistral_7b_l8", (4, 8192), MLP),
    "mellum2_12b_l4_ep4.t8192": ("mellum2_12b_l4_ep4", (2, 8192), ATTN + GATE_UP),
    "keye_vl2_30b_l4_ep8.t16384": ("keye_vl2_30b_l4_ep8", (1, 16384), ATTN + GATE_UP + OUT),
    "lfm2_8b_a1b_l5_ep4.t8192": ("lfm2_8b_a1b_l5_ep4", (2, 8192), CONV + MLP + ATTN + GATE_UP + OUT),
    # no product: in this cell they spared nothing (models/kanana.py:REMAT_RUNGS)
    "kanana2_30b_l5_ep8.t8192": ("kanana2_30b_l5_ep8", (2, 8192), LATENT + SHARED + MLP),
    # experts of two matrices: no gate product; the scan's outputs spared
    # nothing at chunks of 128 (models/nemotron_h.py:REMAT_RUNGS)
    "nemotron3_nano_l9_ep16.t8192": ("nemotron3_nano_l9_ep16", (2, 8192), SHARED + ATTN + UP_OUT),
    # the shared expert's and the dense MLP's products beside them read 13.58
    # GiB on the chip, over what the rule is held to (models/afmoe.py:REMAT_RUNGS)
    "trinity_mini_l5_ep16.t8192": ("trinity_mini_l5_ep16", (2, 8192), ATTN + GATE),
    # the first rung alone: 8.98 GiB of state, each block's halves under a remat of their own
    # and an expert layer's buffers of every assignment leave no rung room (models/kimi_linear.py)
    "kimi_linear_l5_ep32.t8192": ("kimi_linear_l5_ep32", (2, 8192), ()),
    # one Mamba-1 layer's scan output and states (0.2 GiB); the five MLPs' products (3.1) have no room
    "phi4_mini_flash_l5.t16384": ("phi4_mini_flash_l5", (1, 16384), SSCAN),
}
# (cell, names saved after the first rung): the allocator's peak in GiB of
# that step on a v5e (my chip runs, PR 33, calls 1-4: PERF.md section 6; one
# process a set of names, forced; the four-chip cell's is the step's own
# live bytes and reservation, not the reference comparison's peak).
READINGS = {
    ("gpt2_small.t256", ()): 10.80, ("gpt2_small.t256", ATTN): 10.78,
    ("gpt2_small.t256", MLP): 10.79, ("gpt2_small.t256", ATTN + MLP): 12.293,
    ("gpt2_small.t1024", ()): 10.79, ("gpt2_small.t1024", ATTN): 10.78,
    ("gpt2_small.t1024", MLP): 10.80, ("gpt2_small.t1024", ATTN + MLP): 12.296,
    ("mistral_7b_l8.fsdp4_t8192", ()): 9.86, ("mistral_7b_l8.fsdp4_t8192", ATTN): 10.74,
    ("mistral_7b_l8.fsdp4_t8192", MLP): 12.619,
    # the four routed cells: PR 45's programs (my chip runs, PR 45, calls 1 to 7:
    # PERF.md section 6; the benchmark's own `hbm_peak_gib` where the set is the
    # rule's, else one process a set of names, forced, read the same way), the
    # first rung with `moe_plan` in it; with no product among the names the
    # layer in the form that keeps none (call 7). PR 33's two readings of mellum
    # (12.53 with the first rung alone, 13.235 with the operands) were of a step
    # whose expert layer gathered a row for every assignment in float32 (until
    # PR 44).
    ("mellum2_12b_l4_ep4.t8192", ATTN): 12.629,
    ("mellum2_12b_l4_ep4.t8192", ATTN + ("moe_up",)): 13.249,
    ("mellum2_12b_l4_ep4.t8192", ATTN + GATE_UP): 13.401,
    ("keye_vl2_30b_l4_ep8.t16384", ATTN): 10.902,
    ("keye_vl2_30b_l4_ep8.t16384", ATTN + GATE_UP): 11.284,
    ("keye_vl2_30b_l4_ep8.t16384", ATTN + GATE_UP + OUT): 11.558,
    ("lfm2_8b_a1b_l5_ep4.t8192", CONV + MLP + ATTN): 11.296,
    ("lfm2_8b_a1b_l5_ep4.t8192", CONV + MLP + ATTN + GATE_UP + OUT): 12.020,
    ("kanana2_30b_l5_ep8.t8192", LATENT + SHARED + MLP): 12.899,
    # my chip runs, PR 47, calls 3 and 5: one process a set of names, forced,
    # the step alone (the benchmark's own `hbm_peak_gib` in this cell is the
    # reference comparison's peak, 13.54)
    ("nemotron3_nano_l9_ep16.t8192", ()): 12.268,
    ("nemotron3_nano_l9_ep16.t8192", SHARED): 12.316,
    ("nemotron3_nano_l9_ep16.t8192", ATTN): 12.352,
    ("nemotron3_nano_l9_ep16.t8192", UP_OUT): 12.254,
    ("nemotron3_nano_l9_ep16.t8192", SHARED + ATTN + UP_OUT): 12.824,
    # my chip runs, PR 50 (one process a set of names, forced; the compile
    # for the described v5e read 11.97 / 12.92 / 12.92 and 13.47 for the
    # first, second, third and last of these): the first and the third in
    # call 8, the committed program; the others in call 1, the expert layers'
    # buffers at 1.5 times the even load where the family now states 2.0,
    # which moved those two by +0.003 and the compile's three by +0.002
    ("trinity_mini_l5_ep16.t8192", ()): 11.978,
    ("trinity_mini_l5_ep16.t8192", ATTN): 13.047,
    ("trinity_mini_l5_ep16.t8192", ATTN + GATE): 13.044,
    ("trinity_mini_l5_ep16.t8192", GATE): 11.447,
    ("trinity_mini_l5_ep16.t8192", SHARED): 12.009,
    ("trinity_mini_l5_ep16.t8192", MLP): 12.350,
    ("trinity_mini_l5_ep16.t8192", ATTN + GATE + SHARED + MLP): 13.583,
    # my chip run, PR 60, call 2: the step's own live bytes and reservation, three untraced seeds
    # alike (the run's peak, 12.423, is the reference comparison's since); 12.436 before PR 60
    # made the head norm and its gate a kernel pair, 13.318 before PR 55 took the normed q and k
    # and the float32 passes round them out of a KDA half
    ("kimi_linear_l5_ep32.t8192", ()): 12.044,
    # my chip run, PR 57, call 1 (the traced run: the step's own live bytes and reservation)
    ("phi4_mini_flash_l5.t16384", SSCAN): 12.082,
}
# The reckoning against those readings: at most 0.35 GiB under (mistral, the
# first rung alone) and 0.84 over (gpt2_small: its 16 bytes a parameter and
# its head's moment together never were on the chip at once); the routed
# cells' within 0.25 under (mellum) and 0.57 over (kanana: the logits' moment
# beside every gradient's room, which a step still in its forward does not hold).
TOLERANCE_GIB = 0.85


def _cell(name, **changed):
    """(family module, config, StepShape) of a cell, sizes `changed`."""
    config, batch = CELLS[name][:2]
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        sizes = json.load(f)
    sizes.update(changed)
    cfg = families.load(sizes["family"]).build(sizes, sizes["compute_dtype"])
    return (importlib.import_module(type(cfg).__module__), cfg,
            remat.step_shape(batch, sizes["mesh"]))


def _first(family, cfg, shape):
    """A cell's first rung, taken whatever the limit: the flash kernel's
    output and logsumexp, then what the family adds (`moe_plan` where it has
    an expert layer, `attn_sel` where a layer selects its keys)."""
    names = family.remat_plan(cfg, shape, None).names
    assert names[:2] == remat.FIRST_RUNG and set(names[2:]) <= {"moe_plan", "attn_sel"}
    return names


@pytest.mark.parametrize("name", CELLS)
def test_names_are_rungs_of_the_family_and_their_worth_falls_with_the_limit(name, monkeypatch):
    family, cfg, shape = _cell(name)
    rungs = family.REMAT_RUNGS
    first = family.remat_plan(cfg, shape, None)
    assert first.names == _first(family, cfg, shape)
    assert ("moe_plan" in first.names) == hasattr(cfg, "top_k")
    worth = {}  # of a rung's names: ms a GiB x the bytes it holds a layer
    for rung in rungs:
        monkeypatch.setattr(family, "REMAT_RUNGS", (rung,))
        alone = family.remat_plan(cfg, shape, 1024 * GIB)
        assert alone.names == first.names + rung[0]
        worth[rung[0]] = rung[1] * (alone.layer_bytes - first.layer_bytes)
    monkeypatch.setattr(family, "REMAT_RUNGS", rungs)
    spared = []
    for quarter_gib in range(4 * 64, 0, -1):  # 64 GiB down to a quarter
        plan = family.remat_plan(cfg, shape, quarter_gib * GIB // 4)
        taken = [names for names, _ in rungs if set(names) <= set(plan.names)]
        assert plan.names == first.names + tuple(n for names in taken for n in names)
        assert plan.saved_bytes == cfg.n_layer * plan.layer_bytes
        if taken:
            assert plan.reckoned_bytes <= plan.limit_bytes < quarter_gib * GIB // 4
        spared.append(sum(worth[names] for names in taken))
    assert spared == sorted(spared, reverse=True)
    assert spared[0] == sum(worth.values()) and spared[-1] == 0


def test_of_two_rungs_that_do_not_fit_together_the_one_that_spares_more_is_taken():
    """Mistral-7B's cell: the operands spare more a byte, `mlp_up` more of
    the step; both do not fit a v5e, and the rule takes `mlp_up`. With room
    for the operands alone it takes those, where a prefix would take none."""
    family, cfg, shape = _cell("mistral_7b_l8.fsdp4_t8192")
    per_gib = dict((names, worth) for names, worth in family.REMAT_RUNGS)
    assert per_gib[ATTN] > per_gib[MLP]
    assert family.remat_plan(cfg, shape, V5E_LIMIT).names == remat.FIRST_RUNG + MLP
    assert family.remat_plan(cfg, shape, 13 * GIB).names == remat.FIRST_RUNG + ATTN
    assert family.remat_plan(cfg, shape, 24 * GIB).names == remat.FIRST_RUNG + MLP + ATTN


@pytest.mark.parametrize("name,saved", sorted(READINGS))
def test_reckoned_bytes_are_held_to_the_chip_s_reading(name, saved, monkeypatch):
    """The rule's reckoning of a cell's step with these names saved, against
    what a v5e's allocator read of that step."""
    family, cfg, shape = _cell(name)
    monkeypatch.setattr(family, "REMAT_RUNGS", tuple(
        rung for rung in family.REMAT_RUNGS if set(rung[0]) <= set(saved)))
    plan = family.remat_plan(cfg, shape, 1024 * GIB)
    assert set(plan.names) == set(_first(family, cfg, shape) + saved)
    assert abs(plan.reckoned_bytes / GIB - READINGS[name, saved]) <= TOLERANCE_GIB


@pytest.mark.parametrize("name", CELLS)
def test_on_a_v5e_the_rule_takes_what_the_chip_runs_were_made_with(name):
    family, cfg, shape = _cell(name)
    for limit in (V5E_LIMIT, 16909334528):
        plan = family.remat_plan(cfg, shape, limit // GIB * GIB)
        assert plan.names == _first(family, cfg, shape) + CELLS[name][2]
        assert READINGS[name, CELLS[name][2]] < 14.0
        assert plan.reckoned_bytes <= plan.limit_bytes == int(15 * GIB * 0.9)


@pytest.mark.parametrize("rows,seq_len", [(1, 16384), (2, 8192), (1, 32768)])
def test_an_indexed_layer_holds_one_mask_of_its_selection(rows, seq_len):
    """`attn_sel` is the transposed relation's packed mask alone, the one the
    single backward call reads: rows x T x max(128, T/32) int32 words a
    layer. The forward's own orientation is no residual."""
    from ray_tpu.models import mellum

    with open(os.path.join(ROOT, "bench", "configs", "keye_vl2_30b_l4_ep8.json")) as f:
        sizes = json.load(f)
    cfg = families.load(sizes["family"]).build(sizes, sizes["compute_dtype"])
    plan = mellum.remat_plan(cfg, remat.StepShape(rows, seq_len), 1024 * GIB)
    one_mask = rows * seq_len * max(128, seq_len // 32) * 4
    assert "attn_sel" in plan.names and plan.sel_bytes == cfg.n_layer * one_mask
    first = mellum.remat_plan(cfg, remat.StepShape(rows, seq_len), None)
    assert first.names == remat.FIRST_RUNG + ("moe_plan", "attn_sel")
    heads = rows * seq_len * cfg.n_head
    route = rows * seq_len * cfg.top_k * 21  # the choices and the plan: five int32 and a bool
    assert first.layer_bytes == heads * cfg.head_dim * 2 + heads * 4 + one_mask + route


# shapes no chip run was made at: a deeper model or more experts held, at
# the cell's rows a chip, twice and four times as many
UNSEEN = {
    "gpt2_small.t256": dict(n_layer=24),
    "gpt2_small.t1024": dict(n_layer=24),
    "mistral_7b_l8.fsdp4_t8192": dict(num_hidden_layers=12),
    "mellum2_12b_l4_ep4.t8192": dict(num_experts=32),
    "keye_vl2_30b_l4_ep8.t16384": dict(num_experts=32),
    "lfm2_8b_a1b_l5_ep4.t8192": dict(num_experts=16),
    "kanana2_30b_l5_ep8.t8192": dict(n_routed_experts=32),
    "nemotron3_nano_l9_ep16.t8192": dict(n_routed_experts=16),
    "trinity_mini_l5_ep16.t8192": dict(num_experts=16),
    "kimi_linear_l5_ep32.t8192": dict(num_experts=16),
    "phi4_mini_flash_l5.t16384": dict(num_hidden_layers=7, layers_kept=[15, 16, 17, 18, 19, 20, 21]),
}


@pytest.mark.parametrize("name", CELLS)
def test_a_shape_never_seen_gets_fewer_names_and_no_total_over_the_limit(name):
    """(tests/test_tpu_compile.py holds such a plan's total to the bytes of
    the step compiled for a v5e.)"""
    family, cfg, shape = _cell(name, **UNSEEN[name])
    at_cell = family.remat_plan(*_cell(name)[1:], V5E_LIMIT)
    for rows in (shape.rows, 2 * shape.rows, 4 * shape.rows):
        plan = family.remat_plan(cfg, shape._replace(rows=rows), V5E_LIMIT)
        first = _first(family, cfg, shape._replace(rows=rows))
        assert plan.names[:len(first)] == first
        assert len(plan.names) <= len(at_cell.names)
        if plan.names != first:
            assert plan.reckoned_bytes <= plan.limit_bytes


class _Chip:
    """A device as `chip_limit` sees one."""

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        if isinstance(self.limit, Exception):
            raise self.limit
        return None if self.limit is None else {"bytes_limit": self.limit}


def _stream(chips):
    mesh = types.SimpleNamespace(devices=np.array(chips, object), shape={"fsdp": len(chips)})
    return types.SimpleNamespace(mesh=mesh)


def test_a_mesh_whose_first_device_is_another_process_s_gives_the_local_plan(monkeypatch):
    """Under jax.distributed a mesh's first device is process 0's, and no
    other process can ask it. Every process asks its own chips, and chips
    that read a few KiB apart give one limit: the same plan on every host."""
    away = _Chip(jax.errors.JaxRuntimeError("not addressable"))
    here, there = _Chip(16909336064), _Chip(16909334528)
    family, cfg, shape = _cell("mistral_7b_l8.fsdp4_t8192")
    plans = []
    for local, mesh in (([here], [away, here]), ([there], [away, there]),
                        ([here, there], [here, there])):
        monkeypatch.setattr(jax, "local_devices", lambda local=local: local)
        limit = remat.chip_limit(_stream(mesh))
        assert limit == 15 * GIB
        plans.append(family.remat_plan(cfg, shape, limit))
    assert plans[0] == plans[1] == plans[2]
    assert plans[0].names == remat.FIRST_RUNG + MLP


def test_a_chip_that_cannot_say_its_limit_raises_and_a_cpu_device_has_none(monkeypatch):
    assert remat.chip_limit(None) is None  # this box's CPU device
    broken = _Chip(jax.errors.JaxRuntimeError("stats unavailable"))
    monkeypatch.setattr(jax, "local_devices", lambda: [broken])
    with pytest.raises(jax.errors.JaxRuntimeError):
        remat.chip_limit(_stream([broken]))
    # a mesh none of whose devices is this process's (one that is described
    # and not attached) has nobody to ask
    assert remat.chip_limit(_stream([_Chip(V5E_LIMIT)])) is None


@pytest.mark.parametrize("axes", [{"dp": 1}, {"fsdp": 4}, {"dp": 2, "tp": 2},
                                  {"dp": 2, "fsdp": 2, "sp": 2}])
def test_step_shape_splits_the_batch_as_batch_sharding_does(axes):
    n = 1
    for size in axes.values():
        n *= size
    mesh = make_mesh(axes, devices=jax.devices()[:n])
    shape = remat.step_shape((8, 1024), axes)
    assert (shape.rows, shape.seq_len) == batch_sharding(mesh).shard_shape((8, 1024))
    assert shape.state_split == axes.get("fsdp", 1) * axes.get("tp", 1)
    assert shape.tp == axes.get("tp", 1)
    # and what a model on that mesh asks: no limit on a CPU device
    assert remat.chip_limit(stream_sharding(mesh)) is None


def test_the_plan_a_compile_took_is_in_the_flight_recorder_and_the_summary():
    """TrainStep books the plan of the program it just compiled: in the
    `train.compile` event beside the seconds, and as a telemetry gauge."""
    import numpy as np

    from ray_tpu._private import flight_recorder
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.train_step import TrainStep

    ts = TrainStep(GPT2Config.tiny(), make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    state = ts.init(jax.random.PRNGKey(0))
    tok = np.zeros((2, 64), np.int32)
    ts.step(state, ts.shard_batch({"idx": tok, "targets": tok}))
    plan = remat.traced(ts.model.config)
    assert plan == ts.telemetry.remat_plan
    assert plan.names == remat.FIRST_RUNG and plan.limit_bytes is None
    assert remat.traced(GPT2Config.tiny(n_layer=1)) is None  # another model's trace
    assert ts.telemetry.summary()["remat_saved_bytes"] == plan.saved_bytes > 0
    compiles = [e for e in flight_recorder.get_recorder().dump() if e["event"] == "train.compile"]
    seconds, *booked = compiles[-1]["b"]
    assert seconds > 0 and tuple(booked[0]) == plan.names and booked[1:] == list(plan[1:])
