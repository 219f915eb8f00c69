"""The harness is driven by data: cells and metrics are files found by name."""
import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

from bench_testlib import REPO

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def _tree_hash(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" in d:
                continue
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_and_metric_are_files_only(bench_root):
    """A config, a traffic file, a traffic kind, a reader and a metric are
    dropped into a copy of benchmark/; no file that was there changes, and
    the harness runs the new cell and reads the new metric."""
    bdir = os.path.join(bench_root, "benchmark")
    before = _tree_hash(bdir)
    json.dump({"reference": "transformer", "program": {}, "answer": 42},
              open(os.path.join(bdir, "configs", "new-model.json"), "w"))
    json.dump({"kind": "canned", "tokens": 7},
              open(os.path.join(bdir, "traffic", "new_mix.json"), "w"))
    open(os.path.join(bdir, "traffic_kinds", "canned.py"), "w").write(
        "def run(ctx):\n"
        "    ctx.window_opens(); ctx.window_closes()\n"
        "    ctx.memory_peak_bytes = 1\n"
        "    n = ctx.traffic['tokens'] * ctx.config['answer']\n"
        "    return {'attempted': 1, 'failed': 0,\n"
        "            'end_to_end': {'out_tok_s': float(n)},\n"
        "            'compared': {'exact': (0.0, 0.0)},\n"
        "            'stats': {'counters': {'widgets': n}}}\n")
    open(os.path.join(bdir, "readers", "widget_count.py"), "w").write(
        "def read(view, times):\n"
        "    return view['stats']['counters']['widgets'] * times\n")
    json.dump({"reader": "widget_count", "params": {"times": 2}},
              open(os.path.join(bdir, "metrics", "widgets.new.json"), "w"))
    bench = json.load(open(os.path.join(bench_root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "new-model.new_mix",
                               "config": "new-model", "traffic": "new_mix",
                               "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("new-model.new_mix")
    bench["per_layer"].append({
        "name": "widgets.new", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "out_tok_s",
        "workloads": ["new-model.new_mix"]})
    json.dump(bench, open(os.path.join(bench_root, "BENCHMARK.json"), "w"))

    class Dev:
        platform, device_kind = "cpu", "cpu"
    cell = harness.load_cell(bench_root, bdir, "new-model.new_mix")
    res = harness.run_cell(cell, 1, 1.0, False,
                           root=bench_root, bench_dir=bdir, devices=[Dev()])
    assert res["correct"] and res["metrics"]["out_tok_s"]["value"] == 294.0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "compared"
    ctx = harness.Context(bench_dir=bdir, config={}, traffic={}, seed=0,
                          seconds=1, trace=True, devices=[Dev()], t_start=0,
                          trace_dir=bench_root)
    got = harness.per_layer_metrics(
        bdir, cell,
        {"stats": {"counters": {"widgets": 5}}, "end_to_end": {}}, {}, ctx)
    assert got == {"widgets.new": {"value": 10.0, "unit": "rows"}}
    after = _tree_hash(bdir)
    assert {k: after[k] for k in before} == before


NEW_FAMILY = '''"""A family the benchmark has never heard of: its published keys have
other names, and it brings its own module (here it leans on the dense
block's arithmetic; a new architecture would bring its own)."""
from benchmark.references import transformer as dense
from benchmark.weights import Sizes

served_token_gap = dense.served_token_gap
make_params = dense.make_params
CALLS = []


def sizes(cfg):
    CALLS.append(cfg["model_type"])
    return Sizes(layers=cfg["n_layer"], hidden=cfg["d_model"],
                 heads=cfg["n_head"], kv_heads=cfg["n_kv"],
                 head_dim=cfg["d_model"] // cfg["n_head"], ffn=cfg["d_ff"],
                 vocab=cfg["n_vocab"], norm="rms", eps=1e-6, act="swiglu",
                 pos="rope", rope_theta=1e6, max_pos=512, qkv_bias=True,
                 dense_bias=False, tied=False)


def decode_step_bytes(s, dtype, rows, context_tokens):
    return 12345.0
'''


def test_a_new_family_enters_through_the_existing_kinds(bench_root):
    """A configuration of an unknown `model_type`, with a reference module
    of its own, runs through the existing `closed_loop` kind, `systems.py`
    and the roofline reader: nothing shared knows a family by name, and no
    file that was there changes."""
    import jax
    bdir = os.path.join(bench_root, "benchmark")
    before = _tree_hash(bdir)
    tiny = json.load(open(os.path.join(bdir, "configs", "qwen2-tiny.json")))
    new = {"source": "test", "model_type": "newfam", "n_layer": 2,
           "d_model": 128, "n_head": 4, "n_kv": 2, "d_ff": 256,
           "n_vocab": 512, "reduced": [], "reference": "newfam",
           "program": tiny["program"]}
    json.dump(new, open(os.path.join(bdir, "configs", "newfam-tiny.json"),
                        "w"))
    open(os.path.join(bdir, "references", "newfam.py"), "w").write(NEW_FAMILY)
    bench = json.load(open(os.path.join(bench_root, "BENCHMARK.json")))
    name = "newfam-tiny.closed"
    bench["workloads"].append({"name": name, "config": "newfam-tiny",
                               "traffic": "closed_tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2-tiny.closed" in m.get("workloads", []):
            m["workloads"].append(name)
    json.dump(bench, open(os.path.join(bench_root, "BENCHMARK.json"), "w"))
    cell = harness.load_cell(bench_root, bdir, name)
    res = harness.run_cell(cell, 5, 1.0, False, root=bench_root,
                           bench_dir=bdir, devices=jax.devices()[:1])
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["metrics"]["out_tok_s"]["value"] > 0
    module = harness.load_module(bdir, "references", "newfam")
    assert set(module.CALLS) == {"newfam"}
    # the roofline reader takes the family's own bytes function
    roofline = harness.load_module(bdir, "readers", "roofline")
    view = {"trace": {"programs": {"jit_decode_step": {
                "runs": 2, "device_s": 2e-6, "run_s": [1e-6, 1e-6],
                "ops": {}}}},
            "stats": {"counters": {"steps": 1, "rows": 1,
                                   "context_tokens": 1}},
            "config": new, "traffic": {}, "model": module,
            "device_kind": "TPU v5 lite", "chips": 1, "bench_dir": bdir}
    share = roofline.read(view, program="decode_step",
                          bytes_fn="decode_step_bytes")
    assert share == pytest.approx(100 * 12345.0 / 819e9 / 1e-6)
    after = _tree_hash(bdir)
    assert {k: after[k] for k in before} == before


def test_no_shared_code_knows_a_family():
    """Kinds, systems, readers and the harness import no family's library
    and test no `model_type`."""
    bdir = harness.BENCH_DIR
    shared = [os.path.join(bdir, f) for f in (
        "harness.py", "run.py", "control.py", "systems.py", "draws.py",
        "trace_reduce.py")]
    for group in ("traffic_kinds", "readers"):
        shared += [os.path.join(bdir, group, f)
                   for f in os.listdir(os.path.join(bdir, group))
                   if f.endswith(".py")]
    for path in shared:
        text = open(path).read()
        assert "model_type" not in text, path
        assert "benchmark.weights" not in text \
            and "import weights" not in text, path
        assert "benchmark.counts" not in text \
            and "import counts" not in text, path
    assert "model_type" not in open(os.path.join(bdir, "weights.py")).read()


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_is_wired(metric):
    """`moves` names an end-to-end metric that each of the metric's cells
    reports; the metric's file names a reader that exists."""
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {c["name"] for c in BENCH["workloads"]}
    assert metric["moves"] in e2e and metric["moves"] != "setup_s"
    moved = e2e[metric["moves"]]
    assert metric["workloads"], "list the cells that have something to read"
    for cell in metric["workloads"]:
        assert cell in cells
        assert cell in moved.get("workloads", cells)
    spec = harness.load_json(harness.BENCH_DIR, "metrics",
                             metric["name"] + ".json")
    reader = harness.load_module(harness.BENCH_DIR, "readers",
                                 spec["reader"])
    assert callable(reader.read)
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    if metric["name"].split(".")[0].endswith("_roofline") \
            or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def _all_named():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry


@pytest.mark.parametrize("group,entry", list(_all_named()),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_names_units_and_lines(group, entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text
    if group == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
        assert entry["config"] in {c["name"] for c in BENCH["configs"]}
    if group == "configs":
        assert PATH.match(entry["file"])
        assert entry["file"].startswith(tuple(BENCH["paths"]))
        cfg = json.load(open(os.path.join(REPO, entry["file"])))
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
            assert "size" not in key and key in cfg
    if group == "end_to_end":
        assert 0 < entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")


def test_top_level_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for p in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert PATH.match(os.path.relpath(os.path.join(d, f), REPO))
    # every cell reports setup_s, one more end-to-end and a per-layer metric
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell["name"] in m["workloads"] for m in BENCH["per_layer"])
    # no time per token is judged end to end in the closed-loop cell
    assert not any("tpot" in m["name"] for m in BENCH["end_to_end"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_program_section_runs_the_published_sizes(config):
    """The TransformerConfig the program is built from has the widths the
    configuration file publishes (and the reference reads)."""
    from deepspeed_tpu.models import get_model_config
    cfg = harness.load_json(harness.BENCH_DIR, "configs", config + ".json")
    s = harness.load_module(harness.BENCH_DIR, "references",
                            cfg["reference"]).sizes(cfg)
    prog = cfg["program"]
    t = get_model_config(prog["arch"], prog["size"], **prog["overrides"])
    assert (t.num_layers, t.hidden_size, t.num_heads, t.kv_heads,
            t.head_dim, t.ffn_dim, t.vocab_size) == (
        s.layers, s.hidden, s.heads, s.kv_heads, s.head_dim, s.ffn, s.vocab)
    assert t.tie_embeddings == s.tied and t.qkv_bias in (s.qkv_bias, False)
    assert t.norm_eps == s.eps
    if s.pos == "rope":
        assert t.rope_theta == s.rope_theta


def test_off_the_chip_the_command_fails():
    """No TPU: a non-zero exit and no result line, never a CPU number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "TPU" in p.stderr


def test_unknown_cell_is_an_error(bench_root):
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell(bench_root, os.path.join(bench_root, "benchmark"),
                          "no.such_cell")
