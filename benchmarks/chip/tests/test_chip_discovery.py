"""The harness finds every configuration, traffic mix and per-layer metric
by its name in BENCHMARK.json, and a new one is added by adding files and
entries alone."""

import json
import os
import re
import shutil

import pytest

import harness

M = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_resolves():
    for c in M["configs"]:
        assert os.path.isfile(os.path.join(harness.REPO, c["file"]))
        assert harness.load_json("configs", c["name"])["name"] == c["name"]
    for w in M["workloads"]:
        traffic = harness.load_json("traffic", w["traffic"])
        config = harness.load_json("configs", w["config"])
        harness.load_module("sessions", traffic["session"])
        harness.load_module("profiles", traffic["profile"]["kind"])
        harness.load_module("datasets", config["dataset"]["kind"])
        harness.load_module("reference", config["model"]["family"])
        harness.load_module("counts", config["model"]["family"])
        assert set(harness.readers(M, w["name"])) == {
            x["name"] for x in M["per_layer"]
            if w["name"] in x.get("workloads", [w["name"]])}


def test_manifest_shape():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    metrics = M["end_to_end"] + M["per_layer"]
    names = [x["name"] for x in metrics + M["workloads"] + M["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len({x["name"] for x in metrics}) == len(metrics)
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == \
        len(M["workloads"])
    e2e = {x["name"] for x in M["end_to_end"]}
    assert "setup_s" in e2e
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    for x in M["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    for x in M["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert x["moves"] in e2e
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert four_chip_cells_allowed(M)
    assert len(json.dumps(M)) < 64 * 1024


def four_chip_cells_allowed(m: dict) -> bool:
    """At most half the cells, rounded down, ask for four chips; one
    always may."""
    four = sum(w["chips"] == 4 for w in m["workloads"])
    return four <= max(1, len(m["workloads"]) // 2)


def test_four_chip_cells_are_at_most_half():
    one = {"chips": 1}
    four = {"chips": 4}
    assert four_chip_cells_allowed({"workloads": [four]})
    assert four_chip_cells_allowed({"workloads": [four, one, one]})
    assert not four_chip_cells_allowed({"workloads": [four, four, one]})
    assert four_chip_cells_allowed({"workloads": [four, four, one, one]})


STUB_REFERENCE = """
def train(params, x, y, **kw):
    return dict(params)


def evaluate(params, x, y, **kw):
    return {"loss": 1.0}
"""
STUB_COUNTS = """
def forward_macs(model):
    return model["width"] * model["depth"]
"""


def test_a_new_cell_is_files_and_entries(tmp_path):
    """Copy the benchmark, add a configuration of a new model family (its
    config, plain reference and operation count), a traffic mix, a
    per-layer metric and a cell on four chips as new files, and list them
    in the manifest: the harness finds and counts them, and no file that
    was there changes."""
    import counting

    root = tmp_path / "repo"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    m = json.loads(json.dumps(M))
    config = harness.load_json("configs", "paper-cnn")
    config["name"] = "paper-cnn-wide"
    (chip / "configs" / "paper-cnn-wide.json").write_text(json.dumps(config))
    stub = dict(config, name="stub-net",
                model={"family": "stubnet", "width": 64, "depth": 3})
    (chip / "configs" / "stub-net.json").write_text(json.dumps(stub))
    (chip / "reference" / "stubnet.py").write_text(STUB_REFERENCE)
    (chip / "counts" / "stubnet.py").write_text(STUB_COUNTS)
    traffic = harness.load_json("traffic", "modest-diurnal-n100")
    traffic["sample_size"] = 20
    (chip / "traffic" / "modest-wide.json").write_text(json.dumps(traffic))
    (chip / "layers" / "rounds_seen.py").write_text(
        "def read(run):\n    return float(run.window.rounds)\n")
    for name in ("paper-cnn-wide", "stub-net"):
        m["configs"].append(dict(m["configs"][0], name=name,
                                 file=f"benchmarks/chip/configs/{name}.json"))
    m["workloads"] += [
        {"name": "cnn-wide", "config": "paper-cnn-wide",
         "traffic": "modest-wide", "chips": 1, "why": "a test cell"},
        {"name": "stub-four", "config": "stub-net",
         "traffic": "modest-wide", "chips": 4,
         "why": "a test cell on four chips"}]
    m["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                           "better": "higher", "source": "host_clock",
                           "layer": "session", "moves": "wall_s_per_round",
                           "workloads": ["cnn-wide", "stub-four"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    got = harness.manifest(str(root))
    assert four_chip_cells_allowed(got)
    w = harness.workload(got, "cnn-wide")
    assert harness.load_json("configs", w["config"], str(chip))["name"] \
        == "paper-cnn-wide"
    assert harness.load_json("traffic", w["traffic"],
                             str(chip))["sample_size"] == 20
    found = harness.readers(got, "cnn-wide", str(chip))
    assert set(found) == {"rounds_seen"}

    class Win:
        rounds = 7
    assert found["rounds_seen"](type("Run", (), {"window": Win})) == 7.0
    assert "rounds_seen" not in harness.readers(got, "cnn-modest-diurnal",
                                                str(chip))
    four = harness.workload(got, "stub-four")
    assert four["chips"] == 4
    model = harness.load_json("configs", four["config"], str(chip))["model"]
    assert counting.train_flops_per_sample(model, str(chip)) == 6 * 64 * 3
    ref = harness.load_module("reference", model["family"], str(chip))
    assert ref.evaluate({}, None, None) == {"loss": 1.0}
    assert set(harness.readers(got, "stub-four", str(chip))) == {
        "rounds_seen"}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_an_unknown_name_is_an_error():
    with pytest.raises(KeyError):
        harness.workload(M, "no-such-cell")
    with pytest.raises(KeyError):
        harness.load_json("configs", "no-such-config")


def test_build_task_builds_the_cnn_task_as_before():
    from repro.models.tasks import cnn_task

    config = harness.load_json("configs", "paper-cnn")
    got = harness.build_task(config)
    want = cnn_task(harness.train_config(config))
    assert got.cfg == want.cfg and got.tcfg == want.tcfg
    assert got.flat_spec.n == config["params"]


def test_build_task_builds_an_lm_task_from_its_keywords():
    config = {"task": {"factory": "lm_task",
                       "overrides": {"arch": "qwen3-moe-30b-a3b",
                                     "reduce": True, "n_layers": 1,
                                     "vocab": 128}},
              "train": {"optimizer": "sgd", "lr": 0.05, "batch_size": 4}}
    task = harness.build_task(config)
    assert task.cfg.family == "moe" and task.cfg.name == "qwen3-moe-30b-a3b"
    assert task.cfg.n_layers == 1 and task.cfg.vocab == 128
    assert task.cfg.moe_num_experts == 4           # the reduced preset
    assert task.tcfg == harness.train_config(config)


def test_an_unknown_reference_device_is_refused():
    import tiny

    config, traffic = tiny.tiny("cnn-modest-diurnal")
    with pytest.raises(ValueError, match="reference_device"):
        harness.build_cell("x", dict(config, reference_device="tpu"),
                           traffic, 1)


def test_the_chip_reference_is_refused_without_an_accelerator():
    """``reference_device: "chip"`` on a cell whose first device is a CPU
    would run the reference on the host under another name, at limits read
    on a chip: it is refused at build."""
    import tiny

    config, traffic = tiny.tiny("cnn-modest-diurnal")
    with pytest.raises(ValueError, match="accelerator"):
        harness.build_cell("x", dict(config, reference_device="chip"),
                           traffic, 1)
