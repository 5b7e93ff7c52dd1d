"""BENCHMARK.json keeps to its contract, and the harness is driven by data:
a cell or a metric is added by adding files and entries only."""
import json
import re
import shutil

from bench import spec

B = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_keys_and_names():
    assert set(B) == TOP
    assert B["command"][:2] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[k]]
    names += [w["config"] for w in B["workloads"]]
    names += [w["traffic"] for w in B["workloads"]]
    names += [r for c in B["configs"] for r in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in B[k]}) == len(B[k])
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(B)) < 64 * 1024


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    cells = [w["name"] for w in B["workloads"]]
    for c in cells:
        e2e = [m["name"] for m in spec.metrics_for(B["end_to_end"], c)]
        assert "setup_s" in e2e and len(e2e) >= 2, c
        assert spec.metrics_for(B["per_layer"], c), c


def test_moves_is_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = [w["name"] for w in B["workloads"]]
    for m in B["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells), (m, c)


def test_named_files_exist_and_configs_are_used():
    root = spec.ROOT
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])
    for c in B["configs"]:
        assert c["file"].startswith("bench/")
        conf = json.loads((root / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert set(conf.get("published", {})) == set(c["reduced"])
    for w in B["workloads"]:
        cell = spec.cell(B, w["name"])
        assert cell["traffic"]["mode"] in ("serve", "train")
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(spec.reader(m["name"]))


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """Copy the benchmark, add a traffic file, a limits file, a metric
    reader and entries naming them: the harness finds and reads them with
    no code edited."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads(json.dumps(B))
    base = next(w for w in b["workloads"] if w["traffic"] == "chat")
    tr = json.loads((root / "bench/traffic/chat.json").read_text())
    tr["shared_prefix"] = {"count": 4, "len": 512}
    (root / "bench/traffic/chat-shared.json").write_text(json.dumps(tr))
    (root / "bench/limits/olmoe-chat-shared.json").write_text(
        (root / f"bench/limits/{base['name']}.json").read_text())
    (root / "bench/metrics/prompt_tokens.chat-shared.py").write_text(
        "def read(run):\n    return 42.0\n")
    b["workloads"].append(dict(base, name="olmoe-chat-shared",
                               traffic="chat-shared"))
    b["per_layer"].append({"name": "prompt_tokens.chat-shared", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "ttft_p90_ms",
                           "workloads": ["olmoe-chat-shared"]})
    for m in b["end_to_end"]:
        if "olmoe-chat" in m.get("workloads", []):
            m["workloads"].append("olmoe-chat-shared")
    cell = spec.cell(b, "olmoe-chat-shared", root=root)
    assert cell["traffic"]["shared_prefix"]["len"] == 512
    names = [m["name"] for m in cell["per_layer"]]
    assert "prompt_tokens.chat-shared" in names
    assert spec.reader("prompt_tokens.chat-shared", root=root)({}) == 42.0
    from bench import gen
    reqs = gen.serve_requests(cell["traffic"], 50304, 5, "window", 10.0)
    assert all(len(r.prompt) >= 512 + 64 for r in reqs)
