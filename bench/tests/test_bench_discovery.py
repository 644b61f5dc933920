"""Cells, configurations, traffic and metrics are found by name: a new cell
is files, and needs no edit to a file that is there. Also holds
BENCHMARK.json to the shape the harness relies on."""
import json
import re
import shutil

import pytest

from bench import harness
from bench.harness import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench_json():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_resolves_with_its_files_and_readers():
    b = bench_json()
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert harness.load_module("jobs", cell.job).run
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert harness.load_module("metrics", m["name"]).read
        assert set(cell.limits) and all(v > 0 for v in cell.limits.values())


def test_names_and_paths_keep_to_the_contract():
    b = bench_json()
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(b["paths"][0] + "/")
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
    moved = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in moved
        e2e = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e.get("workloads", m["workloads"]))
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["source"] == "device_trace"
    for e in b["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25


# a configuration of another registered architecture: a hybrid of Mamba-2
# and attention layers with experts, whose nested keys and `pattern`
# list resolve to the program's dataclasses, with a reference of its own
HYBRID = {
    "arch": "jamba-1.5-large-398b",
    "model": {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 256,
              "pattern": [["mamba", "moe"], ["attn", "moe"]],
              "moe": {"n_experts": 4, "top_k": 2, "d_ff": 32},
              "ssm": {"d_state": 16, "head_dim": 16, "chunk": 8}},
    "reference": {"module": "hybrid-x", "check_tokens": 40}}
STUB_REFERENCE = """
def init_params(key, shapes, dtype, **weights):
    raise NotImplementedError


hidden = logits = init_params
"""


@pytest.mark.parametrize("conf_name", ["olmo-x", "hybrid-x"])
def test_a_new_cell_is_found_by_name(tmp_path, conf_name):
    """A cell added as files only: a configuration (with its reference,
    where it brings one), a traffic mix and limits, plus its entry in
    BENCHMARK.json."""
    b = bench_json()
    b["workloads"].append({"name": "olmo-serve-overload",
                           "config": conf_name, "traffic": "chat-overload",
                           "chips": 1, "why": "above the knee"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "olmo-serve-chat" in m.get("workloads", []):
            m["workloads"].append("olmo-serve-overload")
    (tmp_path / "bench").mkdir()
    for d in ("configs", "traffic", "limits", "reference"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    conf = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
    conf["name"] = conf_name
    if conf_name == "hybrid-x":
        conf.update(HYBRID)
        (tmp_path / "bench/reference/hybrid-x.py").write_text(STUB_REFERENCE)
    (tmp_path / f"bench/configs/{conf_name}.json").write_text(
        json.dumps(conf))
    chat = json.loads((BENCH / "traffic" / "chat-poisson.json").read_text())
    chat["rate_rps"] *= 1.5
    (tmp_path / "bench/traffic/chat-overload.json").write_text(
        json.dumps(chat))
    (tmp_path / "bench/limits/olmo-serve-overload.json").write_text(
        json.dumps({"logit_gap": 1.0}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = harness.load_cell("olmo-serve-overload", tmp_path)
    assert cell.config["name"] == conf_name and cell.job == "serve"
    module = conf["reference"]["module"]
    assert cell.reference.__file__ == str(
        tmp_path / "bench" / "reference" / f"{module}.py")
    cfg = harness.model_config(cell.config)
    assert cfg.n_layers == conf["model"]["n_layers"]
    if conf_name == "hybrid-x":
        assert cfg.pattern == (("mamba", "moe"), ("attn", "moe"))
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff) == (4, 2, 32)
        assert (cfg.ssm.d_state, cfg.ssm.head_dim, cfg.ssm.chunk) == \
            (16, 16, 8)
    assert cell.traffic["rate_rps"] == chat["rate_rps"]
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    assert "decode_ms.serve" in {m["name"] for m in cell.per_layer}
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", tmp_path)


def test_a_metric_without_workloads_follows_what_it_moves(tmp_path):
    b = bench_json()
    b["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "x", "moves": "itl_p95_ms"})
    b["workloads"].append({"name": "wmt-x", "config": "transformer-wmt",
                           "traffic": "swarm-32x512", "chips": 1,
                           "why": "a training cell"})
    b["end_to_end"].append({"name": "train_tokens_per_s", "unit": "tokens/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock", "workloads": ["wmt-x"]})
    (tmp_path / "bench").mkdir()
    for d in ("configs", "traffic", "limits", "reference"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    (tmp_path / "bench/limits/wmt-x.json").write_text('{"loss_gap": 1}')
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    serve = harness.load_cell("olmo-serve-chat", tmp_path)
    train = harness.load_cell("wmt-x", tmp_path)
    assert "new_metric" in {m["name"] for m in serve.per_layer}
    assert "new_metric" not in {m["name"] for m in train.per_layer}


def test_a_new_reader_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "x.new.py").write_text(
        "def read(r):\n    return 42.0\n")
    mod = harness.load_module("metrics", "x.new", base=tmp_path)
    assert mod.read(None) == 42.0


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
