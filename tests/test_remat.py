"""models/remat.py: which residuals a block's remat saves and in how many
layers, as a pure function of the step's shapes and the chips' bytes_limit.
Over the benchmark's cells and over a limit swept downward: the names are the
first rung and rungs of the family's own, each at a depth (the last k of the
layers that make its names), never fewer than the first rung, never worth
more as the limit falls and never less than the whole rungs that fit there;
what the rule reckons is held to what the chip's allocator read; a shape it
has never seen gets fewer names, not a total over the limit; and every
process of a mesh reckons with the same limit.
"""

import functools
import importlib
import itertools
import json
import os

import jax
import pytest

from bench import families
from ray_tpu.models import remat
from ray_tpu.parallel.mesh import batch_sharding, make_mesh, stream_sharding
from tests._tpu_compile import V5E_LIMIT, V5E_READINGS, V5E_ROOM, Chip, chip_limit_of, stream_on

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = remat.GIB
ATTN = ("attn_q", "attn_k", "attn_v")
MLP = ("mlp_up",)
GATE_UP, OUT = ("moe_gate", "moe_up"), ("moe_out",)  # ops/moe.py:KEPT_PRODUCTS
UP_OUT = ("moe_up", "moe_out")  # ops/moe.py:RELU2.products
CONV, LATENT, SHARED = ("conv_bcu", "conv_y"), ATTN + ("attn_q_shared", "attn_k_shared"), ("shared_up",)
GATE = ("attn_gate",)  # models/layers.py:LlamaAttention's gate projection (models/afmoe.py)
SSCAN = ("sscan_y", "sscan_states")  # ops/selective_scan.py's output and chunk states
SSM = ("ssm_y", "ssm_states")  # ops/ssd.py's output and chunk states
KDA = ("kda_out", "kda_states")  # ops/kda.py's output and chunk states
GDN = ("gdn_out", "gdn_states")  # ops/gdn.py's output and chunk states
# cell: configuration, (B, T) of its traffic, the names the rule takes on a
# v5e after the first rung (the four routed cells' since PR 45, which named
# the expert layer's products and fitted the `block` term again), and each of
# the family's rungs' depth there, in the family's order: (the layers it is
# saved in, the layers that make its names). Since PR 62 a rung too large for
# every layer is saved in the last of them that there is room for; since PR 65
# the limit is the chip's own to within 64 MiB (15.6875 GiB, room 14.119; 15 and
# 13.5 before), and six cells' plans (`DEEPER`) took the layers the 0.69 GiB refused.
CELLS = {
    "gpt2_small.t256": ("gpt2_small", (128, 256), ATTN + MLP, ((12, 12), (12, 12))),
    "gpt2_small.t1024": ("gpt2_small", (32, 1024), ATTN + MLP, ((12, 12), (12, 12))),
    # the operands (38.9 ms a GiB) whole and the MLPs' products (30.7) in the last seven
    # layers of eight, where the rule before PR 62 took the MLPs' whole and no operand
    "mistral_7b_l8.fsdp4_t8192": ("mistral_7b_l8", (4, 8192), MLP + ATTN, ((7, 8), (8, 8))),
    # the down product (5.8 ms a GiB) and the gate's (4.1) whole and the up's in the last
    # three layers of four, where the rule before PR 62 took the gate's and the up's whole
    "mellum2_12b_l4_ep4.t8192": ("mellum2_12b_l4_ep4", (2, 8192), ATTN + GATE_UP + OUT,
                                 ((4, 4), (4, 4), (3, 4), (4, 4))),
    "keye_vl2_30b_l4_ep8.t16384": ("keye_vl2_30b_l4_ep8", (1, 16384), ATTN + GATE_UP + OUT,
                                   ((4, 4),) * 4),
    # the scan's outputs (14.1 ms a GiB) and the MLPs' products (11.2), both whole
    "granite4_h_micro_l10.t4096": ("granite4_h_micro_l10", (1, 4096), SSM + MLP,
                                   ((9, 9), (10, 10))),
    "lfm2_8b_a1b_l5_ep4.t8192": ("lfm2_8b_a1b_l5_ep4", (2, 8192), CONV + MLP + ATTN + GATE_UP + OUT,
                                 ((4, 4), (1, 1), (1, 1), (4, 4), (4, 4), (4, 4))),
    # no product: in this cell they spared nothing (models/kanana.py:REMAT_RUNGS)
    "kanana2_30b_l5_ep8.t8192": ("kanana2_30b_l5_ep8", (2, 8192), LATENT + SHARED + MLP,
                                 ((5, 5), (4, 4), (1, 1))),
    # experts of two matrices: no gate product; the scan's outputs spared
    # nothing at chunks of 128 (models/nemotron_h.py:REMAT_RUNGS)
    "nemotron3_nano_l9_ep16.t8192": ("nemotron3_nano_l9_ep16", (2, 8192), SHARED + ATTN + UP_OUT,
                                     ((4, 4), (1, 1), (4, 4))),
    # every rung whole: the shared expert's and the dense MLP's products beside the operands
    # and the gate's projection read 13.58 GiB on the chip (models/afmoe.py:REMAT_RUNGS)
    "trinity_mini_l5_ep16.t8192": ("trinity_mini_l5_ep16", (2, 8192), ATTN + GATE + SHARED + MLP,
                                   ((5, 5), (5, 5), (4, 4), (1, 1))),
    # 8.98 GiB of state, each block's halves under a remat of their own and an expert layer's
    # buffers of every assignment: the delta rule's outputs in all four KDA layers
    "kimi_linear_l5_ep32.t8192": ("kimi_linear_l5_ep32", (2, 8192), KDA, ((4, 4),)),
    # one Mamba-1 layer's scan output and states (0.2 GiB), and of the five MLPs' products
    # (3.1 GiB) the last four layers'
    "phi4_mini_flash_l5.t16384": ("phi4_mini_flash_l5", (1, 16384), SSCAN + MLP, ((1, 1), (4, 5))),
    "sdar_30b_a3b_l5_ep8.t8192": ("sdar_30b_a3b_l5_ep8", (1, 8192), ATTN + GATE_UP + OUT,
                                  ((5, 5),) * 4),
    "qwen3_next_80b_l5_ep32.t8192": ("qwen3_next_80b_l5_ep32", (2, 8192), GDN + GATE,
                                     ((4, 4), (1, 1))),
}
# The six cells whose plan the grain of 64 MiB moved, and each rung's depth under the limit of
# a whole GiB (15, room 13.5: PR 62's plans, what the chip runs before PR 65 were made with).
# The eight others save every rung their families state at either limit.
DEEPER = {
    "mistral_7b_l8.fsdp4_t8192": ((6, 8), (7, 8)),
    "mellum2_12b_l4_ep4.t8192": ((4, 4), (2, 4), (0, 4), (3, 4)),
    "granite4_h_micro_l10.t4096": ((8, 9), (9, 10)),
    "trinity_mini_l5_ep16.t8192": ((5, 5), (5, 5), (2, 4), (0, 1)),
    "kimi_linear_l5_ep32.t8192": ((3, 4),),
    "phi4_mini_flash_l5.t16384": ((1, 1), (3, 5)),
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
# (cell, each of the family's rungs' depth, in its order): the allocator's
# reading in GiB of the step whose plan saves each rung in the last that many
# of its layers, the step's own live bytes and reservation (my chip runs,
# PR 62: the benchmark's own runs, one process a plan; PERF.md section 6).
# The plans the rule takes on a v5e since it takes a rung by depth, and the
# same cells' at the depths it took before (`READINGS` above, whole rungs).
AT_DEPTH = {
    # call 1, parent and change in turn, three seeds each alike to the MiB: 12.044 at depth 0
    # (`READINGS`), and the compile for the described v5e 12.30 / 12.33 / 12.95 / 13.575 at
    # depths 0 to 3: the reckoning is 0.67 under the chip here, its third moment's doing
    # (PERF.md section 7, "Open after PR 62")
    ("kimi_linear_l5_ep32.t8192", (3,)): 13.616,
    # my chip run, PR 65, call 1: the plan the rule takes under the chip's own limit, all four KDA
    # layers', three seeds alike to the KiB (the parent beside it, depth 3 on PR 63's program,
    # 13.694 twice); the compile for the described v5e 13.934, the reckoning 13.567: 0.41 under
    # the chip where it stood 0.68 under at depth 3
    ("kimi_linear_l5_ep32.t8192", (4,)): 13.982,
    # call 1 likewise (12.082 with no MLP's product; the compile 12.125 / 12.53 at 0 and 3 layers)
    ("phi4_mini_flash_l5.t16384", (1, 3)): 12.540,
    # my chip run, PR 65, call 2: the plan the rule takes under the chip's own limit, the MLP's
    # product in the last four layers of five, three seeds alike to the KiB (the parent beside it
    # 12.540 twice); the compile for the described v5e 13.155, the reckoning 13.924
    ("phi4_mini_flash_l5.t16384", (1, 4)): 13.164,
    # call 6, four chips, parent and change in turn at two seeds, alike to the MiB: the operands
    # in the last two layers of eight beside `mlp_up` whole (12.332 without them there)
    ("mistral_7b_l8.fsdp4_t8192", (8, 2)): 12.701,
    # call 7, four chips: the plan the rule takes, `mlp_up` in six layers and the operands in seven
    ("mistral_7b_l8.fsdp4_t8192", (6, 7)): 12.752,
    # my chip run, PR 65, call 4, four chips, two seeds alike to the KiB (the parent beside it
    # 12.752): the plan the rule takes under the chip's own limit, `mlp_up` in seven layers and the
    # operands whole; the compile for the described v5e 13.464 a chip, the reckoning 14.070
    ("mistral_7b_l8.fsdp4_t8192", (7, 8)): 13.370,
    # call 2, two seeds alike to the MiB: the down product in three layers of four, the gate's
    # in two and no up's (13.367 with the gate's and the up's whole on the same chip)
    ("mellum2_12b_l4_ep4.t8192", (4, 2, 0, 3)): 13.382,
    # my chip run, PR 65, call 3, two seeds alike to the KiB (the parent beside it 13.382 twice):
    # the plan the rule takes under the chip's own limit, the gate's and the down product whole
    # and the up's in three layers of four; the compile for the described v5e 14.044, the
    # reckoning 14.085
    ("mellum2_12b_l4_ep4.t8192", (4, 4, 3, 4)): 13.915,
    # call 2: the shared expert's products in the last two routed layers of four beside the
    # operands and the gate's projection whole (13.121 without them on the same chip)
    ("trinity_mini_l5_ep16.t8192", (5, 5, 2, 0)): 13.165,
    # call 3 of PR 65 likewise, every rung whole: PR 50's reading of the same names to the MiB
    # (`READINGS`: 13.583); the compile for the described v5e 13.455, the reckoning 13.967
    ("trinity_mini_l5_ep16.t8192", (5, 5, 4, 1)): 13.583,
    ("sdar_30b_a3b_l5_ep8.t8192", (5, 5, 5, 5)): 13.222,  # ledger, PR 61: the run's peak
    ("qwen3_next_80b_l5_ep32.t8192", (4, 1)): 12.497,  # ledger, PR 64: the run's peak
}


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


def _made(plan, first, names):
    """The layers that make `names`, from a plan that saves them whole: those
    that hold more than the first rung's bytes."""
    return [i for i, (held, least) in enumerate(zip(plan.layer_bytes, first.layer_bytes))
            if held > least]


@pytest.mark.parametrize("name", CELLS)
def test_depths_are_of_the_family_s_rungs_and_their_worth_falls_with_the_limit(name, monkeypatch):
    """Over a limit swept from 64 GiB down to a quarter: every rung's depth is
    at most the layers that make its names and the layers that save it are the
    last of those; what the plan spares by the rungs' stated worths (ms a GiB
    x the GiB held at that depth) never rises as the limit falls and never
    falls below what the subset of whole rungs that spares most at that limit
    does (the rule before PR 62, so no plan is worth less than it was); and no
    rung has room for a layer more."""
    family, cfg, shape = _cell(name)
    rungs = family.REMAT_RUNGS
    first = family.remat_plan(cfg, shape, None)
    assert first.names == _first(family, cfg, shape)
    assert ("moe_plan" in first.names) == hasattr(cfg, "top_k")
    made, held = {}, {}  # of a rung's names: the layers that make them, their bytes in each
    for rung in rungs:
        alone = _alone(family, cfg, shape, rung, monkeypatch)
        assert alone.names == first.names + rung[0]
        made[rung[0]] = _made(alone, first, rung[0])
        held[rung[0]] = [a - b for a, b in zip(alone.layer_bytes, first.layer_bytes)]
    assert first.depths == tuple((names, 0, len(made[names])) for names, _ in rungs)
    # every subset of whole rungs: its reckoned total and what it spares
    subsets = []
    for k in range(len(rungs) + 1):
        for chosen in itertools.combinations(rungs, k):
            monkeypatch.setattr(family, "REMAT_RUNGS", chosen)
            whole = family.remat_plan(cfg, shape, 1024 * GIB)
            subsets.append((whole.reckoned_bytes,
                            sum(rate * sum(held[names]) for names, rate in chosen)))
    monkeypatch.setattr(family, "REMAT_RUNGS", rungs)
    spared = []
    for quarter_gib in range(4 * 64, 0, -1):  # 64 GiB down to a quarter
        limit = quarter_gib * GIB // 4
        plan = family.remat_plan(cfg, shape, limit)
        assert [names for names, _, _ in plan.depths] == [names for names, _ in rungs]
        assert plan.names == first.names + tuple(
            n for names, k, _ in plan.depths if k for n in names)
        worth, layer_bytes = 0.0, list(first.layer_bytes)
        for (names, k, of), (_, rate) in zip(plan.depths, rungs):
            assert 0 <= k <= of == len(made[names])
            assert plan.depth(names[0]) == k
            for i in made[names][of - k:]:  # the last k of the layers that make them
                layer_bytes[i] += held[names][i]
                worth += rate * held[names][i]
                assert set(names) <= set(plan.by_layer[i])
            for i in made[names][:of - k]:
                assert not set(names) & set(plan.by_layer[i])
        assert plan.layer_bytes == tuple(layer_bytes)
        assert plan.saved_bytes == sum(plan.layer_bytes)
        if plan.names != first.names:
            assert plan.reckoned_bytes <= plan.limit_bytes < limit
        fit_whole = max(w for reckoned, w in subsets if not w or reckoned <= plan.limit_bytes)
        assert worth >= fit_whole * (1 - 1e-12)  # sums of floats, in two orders
        for r, (names, k, of) in enumerate(plan.depths):  # no rung has room for a layer more
            if k < of:
                monkeypatch.setattr(remat, "_depths", lambda *a, r=r: tuple(
                    d + (at == r) for at, (_, d, _) in enumerate(plan.depths)))
                assert family.remat_plan(cfg, shape, limit).reckoned_bytes > plan.limit_bytes
                monkeypatch.undo()
                monkeypatch.setattr(family, "REMAT_RUNGS", rungs)
        spared.append(worth)
    assert all(more >= less * (1 - 1e-12) for more, less in zip(spared, spared[1:]))
    assert spared[0] == pytest.approx(max(w for _, w in subsets), rel=1e-12)
    assert spared[-1] == 0


def _alone(family, cfg, shape, rung, monkeypatch):
    """The family's plan with `rung` its only one, whole."""
    with monkeypatch.context() as patch:
        patch.setattr(family, "REMAT_RUNGS", (rung,))
        return family.remat_plan(cfg, shape, 1024 * GIB)


def test_a_rung_too_large_for_every_layer_is_saved_in_the_last_of_them():
    """Mistral-7B's cell: the operands spare more a byte, `mlp_up` more of
    the step; both do not fit a v5e whole, where the rule before PR 62 took
    `mlp_up` whole and no operand. Under a limit of 15 GiB (the v5e's until
    PR 65 took the chip's own to within 64 MiB) it takes the operands in the
    last seven layers of eight and `mlp_up` in the last six, which spares
    more by the family's stated worths than `mlp_up` whole with the operands
    in two; with less room fewer layers of `mlp_up`, with the v5e's own limit
    the operands whole and `mlp_up` in seven, with room for all of both,
    both."""
    family, cfg, shape = _cell("mistral_7b_l8.fsdp4_t8192")
    (_, per_mlp), (_, per_attn) = family.REMAT_RUNGS
    assert per_attn > per_mlp
    plan = family.remat_plan(cfg, shape, 15 * GIB)
    assert plan.depths == ((MLP, 6, 8), (ATTN, 7, 8))
    assert plan.names == remat.FIRST_RUNG + MLP + ATTN
    assert plan.by_layer == (
        remat.FIRST_RUNG, remat.FIRST_RUNG + ATTN) + (plan.names,) * 6
    assert plan.saved_in(*ATTN) == (False,) + (True,) * 7
    assert plan.saved_in(*MLP) == (False,) * 2 + (True,) * 6
    operand = 8192 * 32 * 128 * 2  # a chip's row of 8,192 tokens, 32 heads of 128 in bf16
    assert plan.layer_bytes[1] - plan.layer_bytes[0] == 3 * operand
    mlp_up = plan.layer_bytes[7] - plan.layer_bytes[1]
    assert mlp_up == 2 * 8192 * 14336 * 2  # the gate's and the up's columns
    worth = lambda mlp, attn: per_mlp * mlp * mlp_up + per_attn * attn * 3 * operand
    assert worth(6, 7) > worth(8, 2) > worth(8, 0)
    assert family.remat_plan(cfg, shape, 13 * GIB).depths == ((MLP, 2, 8), (ATTN, 7, 8))
    assert family.remat_plan(cfg, shape, V5E_LIMIT).depths == ((MLP, 7, 8), (ATTN, 8, 8))
    assert family.remat_plan(cfg, shape, 24 * GIB).depths == ((MLP, 8, 8), (ATTN, 8, 8))


def test_a_name_s_layers_are_the_family_s_to_say():
    """A hybrid family's name is made in some layers only (`made_in`): its
    bytes are counted in those, its depth is out of those, and the layers
    that save it are the last of those."""
    held = remat.Held(always=0, grads=0, logits=0, head=0, block=0)
    rungs = ((("scan_y",), 10.0), (("mlp_up",), 1.0))
    name_bytes = {"attn_out": 4, "attn_lse": 1, "scan_y": 100, "mlp_up": 10}
    made_in = {"attn_out": [2], "attn_lse": [2], "scan_y": [0, 1, 3, 4]}
    limit = lambda room: -(-room * 10 // 9)  # `_LIMIT_SHARE` of it is `room`
    whole = remat.plan(rungs, name_bytes, 5, held, limit(455), made_in=made_in)
    assert whole.depths == ((("scan_y",), 4, 4), (("mlp_up",), 5, 5))
    assert whole.layer_bytes == (110, 110, 15, 110, 110) and whole.saved_bytes == 455
    assert len(set(whole.by_layer)) == 1  # one policy: what a layer does not make is an identity
    # no room for all: the scans, which spare more, whole and the last four MLPs'
    plan = remat.plan(rungs, name_bytes, 5, held, limit(454), made_in=made_in)
    assert plan.depths == ((("scan_y",), 4, 4), (("mlp_up",), 4, 5))
    assert plan.layer_bytes == (100, 110, 15, 110, 110)
    # no room for every scan: the MLPs' whole, and the scans' in layers 4, 3 and 1
    plan = remat.plan(rungs, name_bytes, 5, held, limit(5 + 300 + 50), made_in=made_in)
    assert plan.depths == ((("scan_y",), 3, 4), (("mlp_up",), 5, 5))
    assert plan.layer_bytes == (10, 110, 15, 110, 110)
    assert plan.saved_in("scan_y") == (False, True, True, True, True)  # layer 2 makes none
    assert plan.depth("scan_y") == 3 and plan.depth("mlp_up") == 5 and plan.depth("attn_out") == 0
    # room for neither whole, nor for one scan: the last four MLPs'
    plan = remat.plan(rungs, name_bytes, 5, held, limit(5 + 45), made_in=made_in)
    assert plan.depths == ((("scan_y",), 0, 4), (("mlp_up",), 4, 5))
    assert plan.layer_bytes == (0, 10, 15, 10, 10)
    # the first rung whatever the limit
    assert remat.plan(rungs, name_bytes, 5, held, limit(3), made_in=made_in).layer_bytes == (
        0, 0, 5, 0, 0)


@pytest.mark.parametrize("name,saved", sorted(READINGS))
def test_reckoned_bytes_are_held_to_the_chip_s_reading(name, saved, monkeypatch):
    """The rule's reckoning of a cell's step with these names saved, against
    what a v5e's allocator read of that step."""
    family, cfg, shape = _cell(name)
    monkeypatch.setattr(family, "REMAT_RUNGS", tuple(
        rung for rung in family.REMAT_RUNGS if set(rung[0]) <= set(saved)))
    plan = family.remat_plan(cfg, shape, 1024 * GIB)
    assert set(plan.names) == set(_first(family, cfg, shape) + saved)
    assert all(k == of for _, k, of in plan.depths)
    assert abs(plan.reckoned_bytes / GIB - READINGS[name, saved]) <= TOLERANCE_GIB


# The cell whose plan on a v5e has no reading here: granite's step read 11.382 GiB with both
# rungs whole (my chip runs, PR 65, call 2, two seeds alike; the compile for the described v5e
# 11.252), 11.195 with the scan's outputs in eight layers and the MLPs' products in nine (PR 62,
# call 2, and PR 65's parent; 10.90 with the MLPs' whole and no scan's), and its reckoning stands 1.0 to 2.3 GiB over the chip whatever is saved, outside
# `TOLERANCE_GIB` on the safe side: tests/test_granite.py holds it to that and says why.
NOT_READ = {"granite4_h_micro_l10.t4096"}


@pytest.mark.parametrize("name,depths", sorted(AT_DEPTH))
def test_reckoned_bytes_at_a_depth_are_held_to_the_chip_s_reading(name, depths, monkeypatch):
    """The rule's reckoning of a cell's step with each rung saved in the last
    so many of its layers, against what a v5e's allocator read of that step:
    `Held.total` of the bytes those layers save."""
    family, cfg, shape = _cell(name)
    monkeypatch.setattr(remat, "_depths", lambda rates, at_depth, fits: depths)
    plan = family.remat_plan(cfg, shape, 1024 * GIB)
    assert tuple(k for _, k, _ in plan.depths) == depths
    assert abs(plan.reckoned_bytes / GIB - AT_DEPTH[name, depths]) <= TOLERANCE_GIB


@pytest.mark.parametrize("name", CELLS)
def test_on_a_v5e_the_rule_takes_what_the_chip_runs_were_made_with(name):
    family, cfg, shape = _cell(name)
    for reading in V5E_READINGS:
        plan = family.remat_plan(cfg, shape, chip_limit_of(reading))  # the rule's own rounding
        assert plan.names == _first(family, cfg, shape) + CELLS[name][2]
        assert tuple((k, of) for _, k, of in plan.depths) == CELLS[name][3]
        assert plan.reckoned_bytes <= plan.limit_bytes == V5E_ROOM
        # What the chip's allocator read of that plan's step: still under 14.0 GiB in every
        # cell, though the room is the chip's own since PR 65 (six cells reckon over 13.5 of
        # the 14.12): the fullest readings are kimi_linear's 13.98 and mellum's 13.92, which
        # leave 1.77 GiB of the chip's 15.75. The line is this file's own convention: no
        # harness asks a step to leave any given room, and a deeper plan that reads over it
        # moves it, as long as the step loads.
        depths = tuple(k for k, _ in CELLS[name][3])
        read = AT_DEPTH.get((name, depths), READINGS.get((name, CELLS[name][2])))
        assert (read is None) == (name in NOT_READ)
        assert read is None or read < 14.0


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
    assert len(plan.layer_bytes) == cfg.n_layer and len(set(plan.layer_bytes)) == 1
    first = mellum.remat_plan(cfg, remat.StepShape(rows, seq_len), None)
    assert first.names == remat.FIRST_RUNG + ("moe_plan", "attn_sel")
    heads = rows * seq_len * cfg.n_head
    route = rows * seq_len * cfg.top_k * 21  # the choices and the plan: five int32 and a bool
    assert first.layer_bytes == (heads * cfg.head_dim * 2 + heads * 4 + one_mask + route,) * 4


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
    "granite4_h_micro_l10.t4096": dict(num_hidden_layers=20, layer_types=["mamba"] * 9 + ["attention"]
                                       + ["mamba"] * 9 + ["attention"]),
    "sdar_30b_a3b_l5_ep8.t8192": dict(num_experts=32),
    "qwen3_next_80b_l5_ep32.t8192": dict(num_experts=32),
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


with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    WORKLOADS = [cell["name"] for cell in json.load(f)["workloads"]]


@functools.cache
def _bench_cells():
    """chip_smoke.py's cells by name: (model configuration, one chip's StepShape)."""
    import chip_smoke

    return {name: (cfg, shape) for name, cfg, shape in chip_smoke._cells()}


@pytest.mark.parametrize("name", WORKLOADS)
def test_the_chip_s_own_limit_moves_six_cells_plans_and_leaves_eight(name):
    """Every cell of the benchmark as chip_smoke.py's `remat_plans` builds it
    (`_cells`: the configuration and the traffic's own files), under a v5e's
    limit rounded to a whole GiB (15, until PR 65) and to the rule's grain
    (15.6875): six cells take a rung deeper, none any shallower, with a
    reckoned total that the 13.5 GiB of room refused and the 14.119 hold;
    the eight others saved every rung their families state already and keep
    their plan to the byte, so their lowered steps are the parent's."""
    cfg, shape = _bench_cells()[name]
    family = importlib.import_module(type(cfg).__module__)
    assert (cfg, shape) == _cell(name)[1:]
    before = family.remat_plan(cfg, shape, V5E_READINGS[0] // GIB * GIB)
    after = family.remat_plan(cfg, shape, V5E_LIMIT)
    depths = lambda plan: tuple((k, of) for _, k, of in plan.depths)
    assert depths(after) == CELLS[name][3]
    assert depths(before) == DEEPER.get(name, CELLS[name][3])
    assert before.limit_bytes == int(13.5 * GIB) and after.limit_bytes == V5E_ROOM
    if name in DEEPER:
        assert all(now >= was for now, was in zip(depths(after), depths(before)))
        assert before.reckoned_bytes <= before.limit_bytes < after.reckoned_bytes <= V5E_ROOM
        assert set(before.names) <= set(after.names)
    else:
        assert all(k == of for k, of in depths(after))
        assert after == before._replace(limit_bytes=V5E_ROOM)
    assert set(DEEPER) <= set(WORKLOADS) and len(DEEPER) == 6


def test_the_two_readings_of_a_v5e_give_one_limit():
    """16,909,336,064 and 16,909,334,528, 1.5 KiB apart, are both 251 x 64
    MiB and a remainder: one limit, alone or in one mesh, and the room a
    plan is held to under it 14.119 GiB."""
    limits = {chip_limit_of(reading) for reading in V5E_READINGS} | {chip_limit_of(*V5E_READINGS)}
    assert limits == {V5E_LIMIT} == {251 * GIB // 16}
    assert V5E_ROOM == int(0.9 * 15.6875 * GIB) and 14.118 * GIB < V5E_ROOM < 14.119 * GIB
    assert V5E_READINGS[0] // GIB * GIB == 15 * GIB  # the whole GiB the rule took until PR 65


@pytest.mark.parametrize("reading", [
    *V5E_READINGS, 16 * GIB, 16 * GIB - 1, 32 * GIB + 1, 102_005_473_280, GIB // 16,
    GIB // 16 + 12345])
def test_the_grain_costs_a_chip_under_64_mib(reading):
    """Whatever a chip reads, the limit is a whole number of 64 MiB and less
    than one of them under the reading; a mesh's limit is its least chip's."""
    limit = chip_limit_of(reading)
    assert limit % (64 << 20) == 0 and 0 <= reading - limit < 64 << 20
    assert chip_limit_of(reading + (65 << 20), reading, reading + GIB) == limit


def test_a_mesh_whose_first_device_is_another_process_s_gives_the_local_plan(monkeypatch):
    """Under jax.distributed a mesh's first device is process 0's, and no
    other process can ask it. Every process asks its own chips, and chips
    that read a few KiB apart give one limit: the same plan on every host."""
    away = Chip(jax.errors.JaxRuntimeError("not addressable"))
    here, there = (Chip(reading) for reading in V5E_READINGS)
    family, cfg, shape = _cell("mistral_7b_l8.fsdp4_t8192")
    plans = []
    for local, mesh in (([here], [away, here]), ([there], [away, there]),
                        ([here, there], [here, there])):
        monkeypatch.setattr(jax, "local_devices", lambda local=local: local)
        limit = remat.chip_limit(stream_on(mesh))
        assert limit == V5E_LIMIT
        plans.append(family.remat_plan(cfg, shape, limit))
    assert plans[0] == plans[1] == plans[2]
    assert plans[0].names == remat.FIRST_RUNG + MLP + ATTN and plans[0].depth("mlp_up") == 7


def test_a_chip_that_cannot_say_its_limit_raises_and_a_cpu_device_has_none(monkeypatch):
    assert remat.chip_limit(None) is None  # this box's CPU device
    broken = Chip(jax.errors.JaxRuntimeError("stats unavailable"))
    monkeypatch.setattr(jax, "local_devices", lambda: [broken])
    with pytest.raises(jax.errors.JaxRuntimeError):
        remat.chip_limit(stream_on([broken]))
    # a mesh none of whose devices is this process's (one that is described
    # and not attached) has nobody to ask
    assert remat.chip_limit(stream_on([Chip(V5E_READINGS[0])])) is None


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
    # (seconds, names, bytes a layer, in all, reckoned, limit, ..., the depths, the names a layer)
    assert seconds > 0 and tuple(booked) == plan
    assert plan.depths == ((ATTN, 0, 2), (MLP, 0, 2)) and plan.by_layer == (plan.names,) * 2
