"""A new configuration, cell (its traffic and limits), per-layer metric and
kernel count are picked up from new files alone (and new entries in
BENCHMARK.json), with no edit to a file the benchmark already has."""

import json
import os
import shutil

import harness
import readers


def test_new_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    bench = root / "bench"
    before = {p.relative_to(root): p.read_bytes() for p in root.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}

    # a configuration: its file of sizes and its module beside it
    sizes = json.loads((bench / "configs" / "qwen3-0.6b.json").read_text())
    sizes.update(name="toy-lm", num_hidden_layers=2)
    (bench / "configs" / "toy-lm.json").write_text(json.dumps(sizes))
    shutil.copy(bench / "configs" / "qwen3-0.6b.py", bench / "configs" / "toy-lm.py")
    # a traffic mix
    traffic = json.loads((bench / "traffic" / "ae-2x2x128.json").read_text())
    traffic.update(clients=3, seq=64)
    (bench / "traffic" / "ae-3x2x64.json").write_text(json.dumps(traffic))
    # the limits of its numbers
    (bench / "limits" / "toy-lm.ae.json").write_text(
        json.dumps({"grad_gap": 0.1, "residual_gap": 0.1, "update_gap": 0.1}))
    # a kernel count and a per-layer metric that reads it
    (bench / "kernels" / "toy_kernel.py").write_text(
        'PATTERN = r"toy_kernel"\n\n\ndef count(shape):\n'
        '    return 2.0 * shape["rows"], 4.0 * shape["rows"]\n')
    (bench / "metrics" / "toy_kernel_roofline.py").write_text(
        'def read(ctx):\n    return ctx.roofline("toy_kernel")\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-lm", "source": "https://example.org/toy-lm",
                            "file": "bench/configs/toy-lm.json",
                            "reduced": ["num_hidden_layers"], "why": "a toy"})
    spec["workloads"].append({"name": "toy-lm.ae", "config": "toy-lm",
                              "traffic": "ae-3x2x64", "chips": 1, "why": "a toy"})
    spec["per_layer"].append({"name": "toy_kernel_roofline", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "round_s",
                              "workloads": ["toy-lm.ae"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = {p: (root / p).read_bytes() for p in before}
    assert after == before  # nothing that was there changed

    cell = harness.load_cell("toy-lm.ae", root=str(root))
    assert cell.sizes["num_hidden_layers"] == 2
    assert cell.traffic["clients"] == 3
    assert cell.model.model_flops(cell.sizes, 10, 8) > 0
    assert harness.limits_for(cell, str(bench))["grad_gap"] == 0.1
    assert "toy_kernel_roofline" in [m["name"] for m in cell.per_layer]
    assert "toy_kernel" in readers.kernel_names(str(bench))
    assert "toy_kernel_roofline" in readers.metric_names(str(bench))

    shape = {"rows": 1000}
    reduced = {"rounds": 2, "kernel_s": {"toy_kernel": 1e-6}}
    ctx = readers.Context(cell, {}, reduced, "TPU v5 lite", shape=shape, bench=str(bench))
    value = readers.read("toy_kernel_roofline", ctx)
    # 2 rounds x max(2000 / 197e12, 4000 / 819e9) s over 1e-6 s
    assert abs(value - 100 * 2 * 4000 / 819e9 / 1e-6) < 1e-9
    # an old cell still reads as before, without the new metric
    old = harness.load_cell("qwen3-0.6b.ae", root=str(root))
    assert "toy_kernel_roofline" not in [m["name"] for m in old.per_layer]
