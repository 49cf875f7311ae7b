"""A throwaway benchmark at smoke size in a temporary directory: its own
BENCHMARK.json, configuration, traffic, metric and reference files, found
by name exactly as the real ones are.  Each real cell stands as the tiny
cell of its configuration's ``kind``."""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

TINY_LLM = {
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
    "head_dim": 16, "d_ff": 128, "vocab": 512, "token_ids_below": 500,
}
# the tiny cell that stands for every real cell of a configuration kind
TINY_CELL = {"llm_paged": "tiny.chat", "fixedpoint": "tiny.ppr"}
# a metric that no real cell has: the harness must find it by its name
THROWAWAY_METRIC = '''
def read(run):
    return float(sum(len(r.deliveries) for r in run.in_window())) or None
'''


def tiny_cells(spec: dict, real: Path) -> dict:
    """Each cell of ``spec`` -> the tiny cell of its configuration's kind,
    read from the configuration files under ``real``."""
    files = {c["name"]: c["file"] for c in spec["configs"]}
    kind = {n: json.loads((real / f).read_text())["kind"] for n, f in files.items()}
    return {w["name"]: TINY_CELL[kind[w["config"]]] for w in spec["workloads"]}


def make_bench(tmp: Path, *, real: Path = ROOT) -> Path:
    """The tiny benchmark in ``tmp``, with the metric lists of the benchmark
    in ``real`` (its BENCHMARK.json and the configuration files it names)."""
    spec = json.loads((real / "BENCHMARK.json").read_text())
    tiny = tiny_cells(spec, real)
    (tmp / "bench/configs").mkdir(parents=True)
    (tmp / "bench/traffic").mkdir(parents=True)
    shutil.copytree(ROOT / "bench/metrics", tmp / "bench/metrics")
    shutil.copytree(ROOT / "bench/reference", tmp / "bench/reference",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench/metrics/deliveries_seen.py").write_text(THROWAWAY_METRIC)

    c = json.loads((ROOT / "bench/configs/minicpm-2b.json").read_text())
    c["name"] = "tiny-dense"
    c["model"].update(TINY_LLM)
    # smoke-size readings on this host: the program's widest gap <= 0.003
    # over 4 seeds, the float8 control's >= 0.048
    c["limits"] = {"logit_gap": 0.015}
    (tmp / "bench/configs/tiny-dense.json").write_text(json.dumps(c))
    p = json.loads((ROOT / "bench/configs/pagerank-urand-16386.json").read_text())
    p.update(name="tiny-pagerank", n=60, slots=4, eps=1e-6)
    (tmp / "bench/configs/tiny-pagerank.json").write_text(json.dumps(p))

    t = json.loads((ROOT / "bench/traffic/alpaca.json").read_text())
    t.update(clients=3, ramp_s=0.3, requests=4096, group=4, drain_s=5.0,
             trace_window=[0.2, 0.8])
    t["prompt_tokens"] = {"mean": 6, "sigma": 0.4, "min": 3, "max": 12}
    t["output_tokens"] = {"mean": 12, "sigma": 0.4, "min": 8, "max": 24}
    t["check"] = {"max_tokens": 10000, "min_tokens": 10}
    (tmp / "bench/traffic/tiny_chat.json").write_text(json.dumps(t))
    q = json.loads((ROOT / "bench/traffic/ppr.json").read_text())
    q.update(rate_per_s=20.0, payloads=8, max_iters=400, drain_s=2.0,
             trace_window=[0.2, 0.8])
    (tmp / "bench/traffic/tiny_ppr.json").write_text(json.dumps(q))

    spec["configs"] = [
        {"name": "tiny-dense", "source": "test", "file": "bench/configs/tiny-dense.json",
         "reduced": [], "why": "smoke"},
        {"name": "tiny-pagerank", "source": "test",
         "file": "bench/configs/tiny-pagerank.json", "reduced": [], "why": "smoke"},
    ]
    spec["workloads"] = [
        {"name": "tiny.chat", "config": "tiny-dense", "traffic": "tiny_chat",
         "chips": 1, "why": "smoke"},
        {"name": "tiny.ppr", "config": "tiny-pagerank", "traffic": "tiny_ppr",
         "chips": 1, "why": "smoke"},
    ]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(dict.fromkeys(tiny[w] for w in m["workloads"]))
    spec["per_layer"].append(
        {"name": "deliveries_seen", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "load generator", "moves": "llm_tok_s",
         "workloads": ["tiny.chat"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(tmp: Path, cell: str, seed: int, *, trace: int = 0, seconds: float = 1.5):
    import jax

    from bench import run as run_lib

    args = run_lib.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    return run_lib.run_cell(args, root=tmp, bench_dir=tmp / "bench",
                            devices=jax.devices())
