"""``nemotron_flops.py`` against a count written out layer by layer,
the cut configuration's ``flops.py`` keys against the counts they
stand for (to the FLOP), and the new readers against a run that has
nothing for them."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import loader  # noqa: E402
import nemotron_flops  # noqa: E402

CUT = loader.load_json(
    os.path.join(BENCH, "configs", "nemotron_3_nano_30b_cut.json")
)
NEW_READERS = (
    "ssm.scan_ms_per_step", "ssm.scan_roofline_pct",
    "ssm.mix_ms_per_step", "ssm.proj_ms_per_step", "ssm.state_rms_max",
    "moe.relu2_expert_roofline_pct",
)
SEQ = 8192


def test_the_recurrences_required_work():
    """Per token and head 5 x 64 x 128 FLOPs forward and twice that
    backward, whatever the chunk; the bytes of x, B, C, dt, y and
    their gradients."""
    assert nemotron_flops.layers(CUT, "M") == 8
    per_layer_token = 15 * 64 * 128 * 64
    assert per_layer_token == 7_864_320
    assert nemotron_flops.recurrence_flops_per_step(CUT, 1, SEQ) == (
        per_layer_token * SEQ * 8
    ) == 515_396_075_520
    # forward: x 8192 B, B and C 4096 B, dt 256 B in, y 8192 B out;
    # backward: those and dy in, dx, dB, dC, ddt out
    forward = 8192 + 4096 + 256 + 8192
    backward = forward + 8192 + 4096 + 256
    assert forward + backward == 54_016
    assert nemotron_flops.recurrence_bytes_per_step(CUT, 1, SEQ) == (
        54_016 * SEQ * 8
    )
    # bound by the bytes on a v5e: 4.32 ms against 2.62 ms
    least, bound = flops.roofline_seconds(
        515_396_075_520, 54_016 * SEQ * 8, "TPU v5 lite"
    )
    assert bound == "bytes" and least == pytest.approx(0.004322, rel=1e-3)
    # the chunk size is no part of it
    assert nemotron_flops.recurrence_flops_per_step(
        dict(CUT, chunk_size=256), 1, SEQ
    ) == 515_396_075_520


def test_the_ungated_experts_work_from_the_counted_share():
    assert nemotron_flops.layers(CUT, "E") == 8
    assert nemotron_flops.assignments(CUT, 1, SEQ) == 49152
    assert nemotron_flops.expected_share(CUT) == 0.0625
    # 6 x 3072 rows x 2 matrices x 2688 x 1856 x 8 layers
    assert nemotron_flops.relu2_expert_flops_per_step(
        CUT, 1, SEQ, 0.0625
    ) == 6 * 3072 * 2 * 2688 * 1856 * 8 == 1_471_294_734_336
    # a matrix, three passes: rows x 2688 + rows x 1856 + 8 x 2688 x
    # 1856 elements each; two matrices, 2 bytes, 8 layers
    per_matrix = 3 * (3072 * 2688 + 3072 * 1856 + 8 * 2688 * 1856)
    assert nemotron_flops.relu2_expert_bytes_per_step(
        CUT, 1, SEQ, 0.0625
    ) == 2 * per_matrix * 2 * 8
    # twice the rows: twice the FLOPs, the weights' bytes unchanged
    assert nemotron_flops.relu2_expert_flops_per_step(
        CUT, 1, SEQ, 0.125
    ) == 2 * 1_471_294_734_336


def test_the_whole_step_layer_by_layer_and_by_the_flops_keys():
    """The pattern written out, a layer a line: the matmul parameters
    a token requires, then attention and the recurrence; ``flops.py``
    on the file's GPT-2 keys gives the same number to the FLOP."""
    h = 2688
    mamba = h * (4096 + 6144 + 64) + 4096 * h
    attention = h * 4096 + 2 * h * 256 + 4096 * h
    experts = h * 128 + 2 * h * 3712 + 6 * (8 / 128) * 2 * h * 1856
    assert (mamba, attention, experts) == (
        38_707_200, 23_396_352, 24_041_472.0
    )
    by_layer = {"M": mamba, "*": attention, "E": experts}
    matmul = sum(
        by_layer[kind] for kind in CUT["hybrid_override_pattern"]
    ) + 16384 * h
    assert matmul == nemotron_flops.matmul_params_per_token(CUT)
    assert matmul == 592_822_272
    per_token = (
        6 * matmul
        + 2 * 6 * SEQ * 32 * 128          # two attention layers
        + 8 * 15 * 64 * 128 * 64          # eight recurrences
    )
    assert per_token == 4_022_501_376
    assert nemotron_flops.train_flops_per_token(CUT, SEQ) == per_token
    assert flops.train_flops_per_token(CUT, SEQ) == per_token
    # what the keys stand for
    assert CUT["n_layer"] == nemotron_flops.layers(CUT, "*") == 2
    assert CUT["n_embd"] == 32 * 128 and CUT["n_head"] == 32
    assert flops.attention_flops_per_step(CUT, 1, SEQ) == (
        2 * 6 * SEQ * 4096 * SEQ
    )
    assert CUT["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern",
        "n_routed_experts", "vocab_size",
    ]
    assert CUT["n_routed_experts"] + CUT["first_expert_held"] <= 128


def test_every_published_number_is_the_catalogs():
    """The file holds the source's ``config`` under the same keys;
    what differs is in ``reduced``, with the published value beside
    it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        (row,) = [
            r for r in map(json.loads, f) if r["source_url"] == CUT["source"]
        ]
    for key, value in row["config"].items():
        if key in CUT["reduced"]:
            assert CUT["published"][key] == value, key
        else:
            assert CUT[key] == value, key
    assert CUT["hybrid_override_pattern"] == (
        row["config"]["hybrid_override_pattern"][:18]
    )


def test_the_benchmark_lists_the_cell_and_its_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [
        w for w in bench["workloads"] if w["name"] == "nemotron_steady_8k"
    ]
    assert cell["chips"] == 1 and cell["traffic"] == "steady_8k"
    assert cell["config"] == "nemotron_3_nano_30b_cut"
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["nemotron_steady_8k"]
        reader = loader.load_module("layer_metrics", name)
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
                reader.SOURCE) == tuple(
            listed[name][k]
            for k in ("name", "unit", "layer", "moves", "source")
        )


class NoTrace:
    """A run of a program that has none of this: no trace, no
    counter on its events."""

    trace = None
    config = CUT
    traffic = {"batch": 1, "seq": SEQ}
    report = {"window": {"steps": [{"step": 5}]},
              "device": {"kind": "TPU v5 lite"}}
    flops = flops

    @staticmethod
    def of(type_, **match):
        return [{"type": "train_step", "step": 5, "loss": 1.0}]

    @staticmethod
    def note(line):
        raise AssertionError(f"a silent reader wrote {line!r}")


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name):
    assert loader.load_module("layer_metrics", name).read(NoTrace) is None


class Traced(NoTrace):
    """Six operations of one traced step, a ``%while`` that holds two
    of them among them."""

    trace = {"steps": 1, "busy_s": 0.010, "ops": {
        "%fusion.1": {"seconds": 0.002, "count": 1},
        "%fusion.2": {"seconds": 0.003, "count": 1},
        "%fusion.3": {"seconds": 0.001, "count": 1},
        "%while.4": {"seconds": 0.004, "count": 1},
        "%gmm_fwd.5": {"seconds": 0.002, "count": 1},
        "%copy.6": {"seconds": 0.001, "count": 1},
        "%fusion.7": {"seconds": 0.001, "count": 1},
    }}
    stacks = {
        "%fusion.1": "jit(step)/forward_backward/jvp(block_0)/ssm_scan/dot",
        "%fusion.2": "jit(step)/forward_backward/transpose(jvp(block_0))"
                     "/ssm_scan/dot",
        "%fusion.3": "jit(step)/forward_backward/rematted_computation/"
                     "block_0/ssm_scan/exp",
        "%while.4": "jit(step)/forward_backward/jvp(block_1)/moe_dispatch"
                    "/while",
        "%gmm_fwd.5": "jit(step)/forward_backward/jvp(block_1)/moe_experts"
                      "/gmm_fwd",
        "%copy.6": "",
        "%fusion.7": "jit(step)/forward_backward/jvp(block_0)/ssm_norm/mul",
    }

    @staticmethod
    def of(type_, **match):
        if type_ == "aot_cache":
            return []
        return [{
            "type": "train_step", "step": 5, "moe.held_rows_share": 0.0625,
            "moe.held_tiles_share": 0.08, "ssm.state_rms_max": 0.5,
            "ssm.decay_mean": 0.75,
        }]

    @staticmethod
    def note(line):
        pass


def test_the_readers_split_a_step_by_scope(monkeypatch):
    """Forward, remat copy and backward apart, the ``%while`` left
    out, an operation without a name stack under ``unnamed``."""
    monkeypatch.setattr(
        nemotron_flops.moe_flops, "_stacks_of", lambda path: Traced.stacks
    )
    found = nemotron_flops.by_scope(Traced)
    assert found["ssm_scan"] == {
        "forward": 0.002, "remat": 0.001, "backward": 0.003
    }
    assert found["moe_dispatch"]["forward"] == 0.0
    assert found["unnamed"] == 0.001 and found["other"] == 0.0
    read = lambda name: loader.load_module(  # noqa: E731
        "layer_metrics", name
    ).read(Traced)
    assert read("ssm.scan_ms_per_step") == pytest.approx(6.0)
    assert read("ssm.mix_ms_per_step") == pytest.approx(1.0)
    assert read("ssm.proj_ms_per_step") is None
    assert read("ssm.state_rms_max") == 0.5
    # least 4.322 ms (bytes) over 6 ms
    assert read("ssm.scan_roofline_pct") == pytest.approx(72.03, abs=0.01)
    # 1.4713 TFLOP over 197 TFLOP/s = 7.468 ms over 2 ms: a share
    # over 100 is the made-up trace's, not the reader's to hide
    assert read("moe.relu2_expert_roofline_pct") == pytest.approx(
        373.4, abs=0.1
    )
