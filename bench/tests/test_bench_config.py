"""The configurations' model FLOP counts against hand counts, and
discovery of configurations, traffic, metrics and limits by name."""
import json
import shutil

import pytest

import tiny
from harness import spec

REPO = tiny.REPO


def test_blstm_flops_per_frame_by_hand():
    cell = spec.load_cell(REPO, "blstm-adpsgd-1chip")
    s = cell.sizes
    # per direction and frame: 4 gates x 512 cells x (inputs + recurrent)
    # multiply-adds; layer 0 reads 260 features, layers 1-5 read 1024
    macs = (2 * 4 * 512 * (260 + 512) + 5 * 2 * 4 * 512 * (1024 + 512)
            + 1024 * 256 + 256 * 32000)
    assert macs == 43_073_536
    assert cell.model.fwd_flops_per_frame(s) == 2 * macs
    # forward + backward is 2.58e8 FLOP per frame; 215.04 s of audio
    # (1024 x 21 frames of 10 ms) per step
    assert 3 * 2 * macs == pytest.approx(2.58e8, rel=2e-3)
    assert cell.model.model_flops_per_step(s, cell.traffic) == \
        3 * 2 * macs * 1024 * 21
    assert cell.model.audio_seconds_per_step(s, cell.traffic) == \
        pytest.approx(215.04)


def test_every_cell_finds_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for wl in bench["workloads"]:
        cell = spec.load_cell(REPO, wl["name"])
        assert cell.per_layer(), wl["name"]
        for m in cell.per_layer():
            assert callable(cell.metric_reader(m["name"]).read)
        assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}


def test_files_added_by_name_are_found(tmp_path):
    """A new configuration, traffic mix, generator kind, strategy,
    optimizer, schedule and metric are files and entries only: nothing in
    the harness names them."""
    root = tiny.make_root(tmp_path, name="new-cell")
    bench = root / "bench"
    shutil.copy(bench / "configs" / "tiny-blstm.json",
                bench / "configs" / "other-blstm.json")
    shutil.copy(bench / "configs" / "tiny-blstm.py",
                bench / "configs" / "other-blstm.py")
    for d, old, new in (("generators", "asr_frames", "other_frames"),
                        ("strategies", "ad_psgd", "other_psgd"),
                        ("optimizers", "sgd", "other_sgd"),
                        ("schedules", "paper_recipe", "other_recipe")):
        shutil.copy(bench / d / f"{old}.py", bench / d / f"{new}.py")
    (bench / "traffic" / "other-mix.json").write_text(json.dumps(dict(
        tiny.TRAFFIC, kind="other_frames", strategy="other_psgd",
        optimizer="other_sgd", schedule="other_recipe", learners=2)))
    (bench / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.trace.steps()) or None\n")
    spec_ = json.loads((root / "BENCHMARK.json").read_text())
    spec_["configs"].append(dict(spec_["configs"][0], name="other-blstm",
                                 file="bench/configs/other-blstm.json"))
    spec_["workloads"].append({"name": "other-cell", "config": "other-blstm",
                               "traffic": "other-mix", "chips": 1,
                               "why": "test"})
    spec_["per_layer"].append(
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "device_trace", "layer": "device",
         "moves": "audio_s_per_s_per_chip", "workloads": ["other-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec_))
    (bench / "limits" / "other-cell.json").write_text(
        (bench / "limits" / "new-cell.json").read_text())

    cell = spec.load_cell(root, "other-cell", bench=bench)
    assert cell.config["name"] == "other-blstm"
    assert cell.traffic["kind"] == "other_frames"
    assert len(cell.batches(3)) == tiny.TRAFFIC["distinct_batches"]
    assert cell.batches(3)[0]["features"].shape[0] == 2 * 8
    assert cell.strategy.STALE and cell.optimizer.update
    assert cell.schedule.lr_at(cell.traffic["lr"], 0) == 0.5
    assert "steps_seen" in [m["name"] for m in cell.per_layer()]
    assert cell.metric_reader("steps_seen").read is not None
    assert "steps_seen" not in [
        m["name"] for m in spec.load_cell(root, "new-cell",
                                          bench=bench).per_layer()]
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell", bench=bench)
