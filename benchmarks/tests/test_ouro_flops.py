"""``ouro_flops.py`` against hand-worked numbers, the cut
configuration's ``flops.py`` keys against the count they stand for,
and the new cell and its readers as ``BENCHMARK.json`` lists them,
BY NAME (a later PR's entries come after them)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import loader  # noqa: E402
import ouro_flops  # noqa: E402

CUT = loader.load_json(os.path.join(BENCH, "configs", "ouro_2_6b_cut.json"))
NEW_READERS = (
    "loop.blocks_ms_per_step", "loop.blocks_peak_pct",
    "loop.exit_gate_ms_per_step", "loop.expected_exit",
    "loop.exit_entropy",
)


def test_an_application_and_an_exit_by_hand():
    cfg = {
        "hidden_size": 2048, "num_attention_heads": 16, "head_dim": 128,
        "intermediate_size": 5632, "vocab_size": 49152,
        "num_hidden_layers": 12, "total_ut_steps": 4,
    }
    assert ouro_flops.applications(cfg) == 48
    # q, k, v, o at 2048 x 2048 and three SwiGLU matrices at 2048 x 5632
    assert ouro_flops.block_matmul_params(cfg) == (
        4 * 2048 * 2048 + 3 * 2048 * 5632
    ) == 51_380_224
    # 6 a matmul parameter + causal attention 6 x seq x width
    assert ouro_flops.block_flops_per_token(cfg, 4096) == (
        6 * 51_380_224 + 6 * 4096 * 2048
    ) == 358_612_992
    assert ouro_flops.exit_flops_per_token(cfg) == 6 * 49152 * 2048
    # 48 applications and FOUR exits: 19.63 GFLOP a token
    assert ouro_flops.train_flops_per_token(cfg, 4096) == (
        48 * 358_612_992 + 4 * 603_979_776
    ) == 19_629_342_720
    # a quarter of the passes: a quarter of the stack, a quarter of the
    # exits
    once = dict(cfg, total_ut_steps=1)
    assert ouro_flops.train_flops_per_token(once, 4096) == (
        19_629_342_720 / 4
    )
    # the stack alone, one step of 4096 tokens: 70.5 TFLOP, 357.9 ms at
    # the v5e's peak, bound by FLOPs (the bytes take 28.0 ms)
    work = ouro_flops.blocks_flops_per_step(cfg, 1, 4096)
    assert work == 48 * 358_612_992 * 4096
    moved = ouro_flops.blocks_bytes_per_step(cfg, 1, 4096)
    assert moved == 48 * (4 * 51_380_224 + 4 * 4096 * 2048) * 2
    least, bound = flops.roofline_seconds(work, moved, "TPU v5 lite")
    assert bound == "flops" and least == pytest.approx(0.35790, rel=1e-4)


def test_the_gpt2_keys_count_what_ouro_flops_counts():
    """``flops.py`` reads ``n_layer`` 48 (applications, not blocks
    held) and ``n_inner`` 9984 (the SwiGLU as two matrices + the three
    further exits' heads spread over the applications): the same
    number to the FLOP, so ``model.mfu_pct`` is tokens/s x 19.63e9 /
    197e12."""
    assert (CUT["n_layer"], CUT["n_embd"], CUT["n_head"],
            CUT["n_inner"]) == (48, 2048, 16, 9984)
    assert CUT["n_inner"] == 8448 + 1536
    assert 2 * 8448 == 3 * 5632
    assert 1536 * 48 * 2 * 2048 == 3 * 49152 * 2048
    assert CUT["n_layer"] == ouro_flops.applications(CUT)
    assert flops.matmul_params(CUT) == (
        48 * ouro_flops.block_matmul_params(CUT) + 4 * 49152 * 2048
    )
    for seq in (1024, 4096, 65536):
        assert flops.train_flops_per_token(CUT, seq) == (
            ouro_flops.train_flops_per_token(CUT, seq)
        )
    assert flops.attention_flops_per_step(CUT, 1, 4096) == (
        48 * 6 * 4096 * 2048 * 4096
    )
    # what kernel.flash_roofline_pct divides by: 50.2 ms
    least, bound = flops.roofline_seconds(
        flops.attention_flops_per_step(CUT, 1, 4096),
        flops.attention_bytes_per_step(CUT, 1, 4096), "TPU v5 lite",
    )
    assert bound == "flops" and least == pytest.approx(0.05023, rel=1e-3)


def test_the_cell_and_its_readers_are_listed_by_name():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells["ouro_steady_1x4k"] == {
        "name": "ouro_steady_1x4k", "config": "ouro_2_6b_cut",
        "traffic": "steady_1x4k", "chips": 1,
        "why": cells["ouro_steady_1x4k"]["why"],
    }
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs["ouro_2_6b_cut"]["file"] == (
        "benchmarks/configs/ouro_2_6b_cut.json"
    )
    assert configs["ouro_2_6b_cut"]["reduced"] == CUT["reduced"] == [
        "num_hidden_layers", "layer_types",
    ]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        entry = listed[name]
        assert entry["workloads"] == ["ouro_steady_1x4k"], name
        assert (entry["layer"], entry["moves"]) == (
            "looped stack", "tokens_per_s"
        ), name
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            entry[k] for k in ("name", "unit", "layer", "moves", "source")
        )
    # the accepted metrics without a list report in the new cell too
    for metric in bench["per_layer"]:
        if "workloads" not in metric:
            assert metric["moves"] in ("tokens_per_s", "setup_s")


class Bare:
    """A run of the parent: no trace, no counters."""

    trace = None
    config = CUT
    traffic = {"batch": 1, "seq": 4096}
    report = {"window": {"steps": [{"step": 5}]},
              "device": {"kind": "TPU v5 lite"}}

    def of(self, type_, **match):
        return []

    def note(self, line):
        raise AssertionError(line)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_returns_nothing_where_nothing_is_to_read(name):
    assert loader.load_module("layer_metrics", name).read(Bare()) is None
