"""The harness is driven by data: a later PR adds a configuration, a traffic
mix, a cell and a per-layer metric as NEW files and NEW entries of
BENCHMARK.json, and edits no file that exists. Proven on a temp copy: every
file of the copy is hashed before the additions and after, and the harness runs
the added cell and reports the added metric."""
import hashlib
import json
import os

import common


def tree_hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_a_cell_a_config_and_a_metric_are_added_as_files(tmp_path):
    root = common.tiny_copy(tmp_path)
    bench_dir = os.path.join(root, "benchmark")
    before = tree_hashes(bench_dir)

    # a configuration: another GPT-2 size, its own file, the same reference
    cfg = json.load(open(os.path.join(bench_dir, "configs", "gpt2_xl.json")))
    cfg.update(n_embd=32, n_head=2, n_layer=1, n_inner=64)
    json.dump(cfg, open(os.path.join(bench_dir, "configs", "gpt2_dummy.json"),
                        "w"))
    # a traffic mix: another rate and other lengths, parameters only
    job = json.load(open(os.path.join(bench_dir, "traffic", "chat.json")))
    job["arrivals"].update(rate_per_s=8.0)
    job["prompt_len"].update(median=12, max=40)
    json.dump(job, open(os.path.join(bench_dir, "traffic", "dummy_fast.json"),
                        "w"))
    # a per-layer metric: a reader of its own
    with open(os.path.join(bench_dir, "layer_metrics",
                           "dummy_prefills_per_s.py"), "w") as f:
        f.write('"""prefills per second of the window, from the engine\'s '
                'counter."""\n\n\ndef read(obs, run):\n'
                '    tele = obs.get("telemetry")\n'
                '    return tele["prefills"] / obs["window_s"] if tele '
                'else None\n')
    # and their entries
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "gpt2_dummy", "source": "test", "reduced": [], "why": "test",
        "file": "benchmark/configs/gpt2_dummy.json"})
    bench["workloads"].append({
        "name": "gpt2_dummy.fast", "config": "gpt2_dummy",
        "traffic": "dummy_fast", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "dummy_prefills_per_s", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "serving scheduler",
        "moves": "itl_p90_ms", "workloads": ["gpt2_dummy.fast"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "gpt2xl_chat" in m["workloads"]:
            m["workloads"].append("gpt2_dummy.fast")
    json.dump(bench, open(path, "w"))

    after = tree_hashes(bench_dir)
    assert {k: after[k] for k in before} == before      # nothing edited
    assert set(after) - set(before) == {
        "configs/gpt2_dummy.json", "traffic/dummy_fast.json",
        "layer_metrics/dummy_prefills_per_s.py"}

    rc, result, out, err = common.steered_run(root, "gpt2_dummy.fast", 0)
    assert rc == 0 and result["correct"], (out[-3000:], err[-3000:])
    assert {"itl_p90_ms", "setup_s"} == set(result["metrics"])
    rc, result, out, err = common.steered_run(root, "gpt2_dummy.fast", 1)
    assert rc == 0 and result["correct"], (out[-3000:], err[-3000:])
    assert result["metrics"]["dummy_prefills_per_s"]["value"] > 0
    assert result["metrics"]["dummy_prefills_per_s"]["unit"] == "1/s"
    assert "batch_occupancy_pct" in result["metrics"]
    # an existing cell still runs beside the added one
    rc, result, out, err = common.steered_run(root, "gpt2xl_chat", 0)
    assert rc == 0 and result["correct"], (out[-3000:], err[-3000:])
