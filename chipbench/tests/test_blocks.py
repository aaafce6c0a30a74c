"""Block files found by name, and the reference that keeps its float32
state on the host. Run by hand, on the CPU, with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

* The reference's readings at two toy dense sizes (the tied MiniCPM layout,
  and an untied head with GQA 6/2) equal, bit for bit, those that the
  whole-model reference gave before its float32 gradient and AdamW moments
  moved to the host. ``RECORDED`` was taken with that reference
  (``reference/train.py:steps`` as it stood then, its gradient and moments
  whole-model arrays on the device), on the CPU with jax 0.9.0, seed
  3000000019, batch 16, the configuration ``configs/minicpm-2b.json`` with
  ``SMALL``'s widths and the traffic ``graft-s1`` cut as ``TRAFFIC_CUT``
  cuts it: ``repr`` of each float, which reads back to the same double.
* ``flops.graft_step_flops`` for ``configs/minicpm-2b.json`` equals the
  dense formula the count used before it moved into the block file.
* A block file the tests bring (``tests/blocks/scaled.py``, one key more
  than the dense block) runs through ``harness.experiment``, the
  reference and a whole run of the harness, with no benchmark file
  changed: the loader is pointed at the tests' directory.
* A key that the configuration's block does not declare is refused, and a
  traffic file's unknown key too.
"""
from __future__ import annotations

import copy
import json
import math
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from chipbench import correct, flops, harness  # noqa: E402
from chipbench.reference import blocks  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 3_000_000_019
CELL = "minicpm-2b.graft-s1"
SMALL = {"hidden_size": 72, "intermediate_size": 144,
         "num_attention_heads": 6, "head_dim": 12, "num_hidden_layers": 2,
         "vocab_size": 257}
CASES = {
    "tied": dict(SMALL, num_key_value_heads=6),
    "untied_gqa": dict(SMALL, num_key_value_heads=2,
                       tie_word_embeddings=False),
}
TRAFFIC_CUT = {"seq_len": 64, "probe_positions": 16, "loss_chunk": 16,
               "pool_steps": 8}
SMALL_LIMITS = {"loss": 1e-4, "grad_norm": 5e-3, "param_change": 6e-3,
                "selection": 0.05}

# (pivots, rank) of each refresh, and (pivot_margin, rank_margin)
RECORDED = {
    'tied' : {
        "losses": [5.54326868057251, 5.568780183792114, 5.555055320262909],
        "grad_norms": {
            'blocks/attn/wk': 0.06448832899332047,
            'blocks/attn/wo': 0.11893431097269058,
            'blocks/attn/wq': 0.061687275767326355,
            'blocks/attn/wv': 0.12068255245685577,
            'blocks/ln1': 0.01728859171271324,
            'blocks/ln2': 0.011914810165762901,
            'blocks/mlp/w_down': 0.10770116746425629,
            'blocks/mlp/w_gate': 0.07532308250665665,
            'blocks/mlp/w_up': 0.07464128732681274,
            'embed': 0.8936148285865784,
            'final_norm': 0.009430725127458572,
        },
        "change_norms": {
            'blocks/attn/wk': 0.03518088534474373,
            'blocks/attn/wo': 0.0354912132024765,
            'blocks/attn/wq': 0.03465256839990616,
            'blocks/attn/wv': 0.03578955680131912,
            'blocks/ln1': 0.004537936765700579,
            'blocks/ln2': 0.004078727215528488,
            'blocks/mlp/w_down': 0.05414925515651703,
            'blocks/mlp/w_gate': 0.04951970651745796,
            'blocks/mlp/w_up': 0.050046514719724655,
            'embed': 0.05054713040590286,
            'final_norm': 0.0033731074072420597,
        },
        "decided": [
            ([13, 7, 14, 1, 3, 12, 9, 10], 8),
            ([0, 14, 4, 12, 15, 5, 7, 9], 4),
            ([5, 2, 3, 10, 14, 15, 6, 0], 8),
        ],
        "margins": [
            (0.03496564219304386, 0.05569359311137445),
            (0.03585389493446037, 0.010578748731387755),
            (0.006623467059661776, 0.044471630440619636),
        ],
    },
    'untied_gqa' : {
        "losses": [6.040335059165955, 5.985886335372925, 6.209853887557983],
        "grad_norms": {
            'blocks/attn/wk': 0.0863372254571863,
            'blocks/attn/wo': 0.1480853267216686,
            'blocks/attn/wq': 0.08439381555873743,
            'blocks/attn/wv': 0.13821294398472572,
            'blocks/ln1': 0.024287788470633283,
            'blocks/ln2': 0.015339319503641046,
            'blocks/mlp/w_down': 0.12954549809024077,
            'blocks/mlp/w_gate': 0.09163004935977825,
            'blocks/mlp/w_up': 0.10130879795807778,
            'embed': 0.9486783059397568,
            'final_norm': 0.02130101689315367,
            'lm_head': 0.08763822114053751,
        },
        "change_norms": {
            'blocks/attn/wk': 0.020767778158187866,
            'blocks/attn/wo': 0.034948188811540604,
            'blocks/attn/wq': 0.034790340811014175,
            'blocks/attn/wv': 0.019677020609378815,
            'blocks/ln1': 0.00440204469487071,
            'blocks/ln2': 0.004310209304094315,
            'blocks/mlp/w_down': 0.0537356398999691,
            'blocks/mlp/w_gate': 0.0498587042093277,
            'blocks/mlp/w_up': 0.04960423707962036,
            'embed': 0.04539775848388672,
            'final_norm': 0.004386677872389555,
            'lm_head': 0.051333069801330566,
        },
        "decided": [
            ([7, 12, 4, 10, 1, 2, 3, 14], 4),
            ([10, 2, 13, 7, 9, 0, 14, 4], 4),
            ([14, 12, 5, 7, 9, 2, 8, 11], 4),
        ],
        "margins": [
            (0.0004622044392860749, 0.0695870205964384),
            (0.023866808516020726, 0.03359025215134859),
            (0.05950528705402005, 0.043192725997516224),
        ],
    },
}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_readings_unchanged(case):
    from chipbench.reference import train as ref_train
    config = dict(load("chipbench/configs/minicpm-2b.json"), **CASES[case])
    traffic = dict(load("chipbench/traffic/graft-s1.json"), **TRAFFIC_CUT)
    got = ref_train.steps(config, traffic, 16, SEED)
    want = RECORDED[case]
    assert got["losses"] == want["losses"]
    assert got["grad_norms"] == want["grad_norms"]
    assert got["change_norms"] == want["change_norms"]
    assert [([int(r) for r in d["pivots"]], int(d["rank"]))
            for d in got["decided"]] == want["decided"]
    assert [(d["pivot_margin"], d["rank_margin"])
            for d in got["margins"]] == want["margins"]


def dense_step_flops(c, batch, seq, probe_positions, rank, refresh):
    """The count as it stood before the block files: the dense layer."""
    d, f, h, g, dh = (c["hidden_size"], c["intermediate_size"],
                      c["num_attention_heads"], c["num_key_value_heads"],
                      c["head_dim"])
    layers, vocab = c["num_hidden_layers"], c["vocab_size"]

    def layer(rows):
        tokens = rows * seq
        proj = 2 * d * (h + 2 * g) * dh + 2 * h * dh * d
        mlp = 3 * 2 * d * f
        attn = rows * 2 * 2 * h * dh * seq * ((seq + 1) / 2)
        return tokens * (proj + mlp) + attn

    stride = max(1, seq // probe_positions) if probe_positions else 1
    select = layers * layer(batch) + 2.0 * d * vocab * (
        batch * len(range(0, seq, stride))) if refresh else 0.0
    return select + 3.0 * (layers * layer(rank)
                           + 2.0 * d * vocab * (rank * seq))


@pytest.mark.parametrize("refresh", [True, False])
@pytest.mark.parametrize("rank", [2, 4, 8])
def test_step_flops_unchanged(rank, refresh):
    c = load("chipbench/configs/minicpm-2b.json")
    t = load("chipbench/traffic/graft-s1.json")
    args = (t["batch_per_chip"], t["seq_len"], t["probe_positions"], rank,
            refresh)
    assert flops.graft_step_flops(flops.Dims.from_config(c), *args) == \
        dense_step_flops(c, *args)


@pytest.fixture
def test_blocks(monkeypatch):
    """The loader also looks in the tests' own block directory."""
    monkeypatch.setattr(blocks, "DIRS", blocks.DIRS + [
        os.path.join(HERE, "blocks")])
    monkeypatch.setattr(correct, "limits", lambda workload: SMALL_LIMITS)


def small_cell(**config) -> harness.Cell:
    cell = harness.find_cell(CELL)
    cell.config.update(CASES["tied"], **config)
    cell.traffic.update(TRAFFIC_CUT)
    return cell


def scaled_cell(scale: float = 0.5) -> harness.Cell:
    return small_cell(block="scaled", attn_query_scale=scale)


def test_block_found_by_name(test_blocks):
    from chipbench import traffic as traffic_lib
    from chipbench.reference import train as ref_train
    traffic_lib.register()
    cell = scaled_cell()
    cfg = harness.experiment(cell, SEED)
    assert cfg.model.overrides["query_scale"] == 0.5
    assert cfg.model.overrides["num_heads"] == 6
    got = ref_train.steps(cell.config, cell.traffic, cell.batch, SEED)
    base = ref_train.steps(small_cell().config, cell.traffic, cell.batch,
                           SEED, n_steps=1)
    assert all(math.isfinite(v) for v in got["losses"])
    assert got["losses"][0] != base["losses"][0]
    assert flops.Dims.from_config(cell.config).block is blocks.load("scaled")


def test_block_runs_through_the_harness(test_blocks):
    """The program runs with the block's key, and the reference reads it:
    the run is correct against the block that declares it, and not against
    the dense block, which scores queries by ``1 / sqrt(head_dim)``."""
    def against_dense(cell, seed, prog):
        dense = copy.deepcopy(cell)
        del dense.config["block"], dense.config["attn_query_scale"]
        return correct.check(dense, seed, prog)

    run = lambda check=None: harness.run(  # noqa: E731
        scaled_cell(), SEED, 0.1, False, time.time(), require_tpu=False,
        check=check)
    assert run()["correct"]
    assert not run(against_dense)["correct"]


@pytest.mark.parametrize("where", ["config", "traffic", "other_block"])
def test_undeclared_key_is_refused(where, test_blocks):
    cell = small_cell()
    if where == "config":
        cell.config["qk_layernorm"] = True
    elif where == "traffic":
        cell.traffic["qk_layernorm"] = True
    else:                      # the scaled block's key under the dense block
        cell.config["attn_query_scale"] = 2.0
    with pytest.raises(ValueError, match="keys the benchmark does not run"):
        harness.experiment(cell, SEED)


def test_unknown_block_is_refused():
    with pytest.raises(KeyError, match="no block file"):
        harness.experiment(small_cell(block="no_such_block"), SEED)
