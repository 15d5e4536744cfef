"""A cell of the benchmark at a size the CPU runs in seconds."""
from __future__ import annotations

import copy
import time

from chipbench import harness

CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e11, "hbm_bytes_per_s": 1e10}}
SEED = 2 ** 33 + 11

# a predict cell, which the benchmark does not run yet (PERF.md §7), run
# through the same generator and check as a mix file would give it
PREDICT = {"name": "netflix.serve_predict", "config": "netflix",
           "traffic": "serve_predict_open", "chips": 1}
PREDICT_MIX = {"kind": "serve", "query": "predict", "pinned_modes": [0, 2],
               "candidate_mode": 1,
               "size": {"dist": "loguniform", "min": 1, "max": 256},
               "arrivals": {"process": "poisson", "rate_per_s": 100},
               "check_requests": 0,
               "warm_sizes_upto": 4, "trace_seconds": 0.2}
# the CPU computes predict in float32 (gap ~1e-7) and the float8 control
# reads ~1e-1 at this size: a limit between them for the test alone
PREDICT_LIMITS = {"never_answered": {"limit": 0},
                  "predict_gap": {"limit": 1e-3}}


# the bursty top-k cell of PERF.md §7: the top-k mix with on/off
# arrivals (``arrivals/onoff.py``), a mix that needs no new code
BURST = {"name": "yahoo.serve_topk_burst", "config": "yahoo_music",
         "traffic": "serve_topk_burst", "chips": 1}
BURST_ARRIVALS = {"process": "onoff", "rate_per_s": 2800, "on_s": 0.5,
                  "off_s": 1.5}
EXTRA = {PREDICT["name"]: PREDICT, BURST["name"]: BURST}

# the faults a cell can have and its control, by the kind and query of
# its mix: every cell, one added as files alone too, runs its kind's
# list at its own shrunk configuration (``test_bench_faults.py``)
FAULTS = {
    ("train", None): [{"fault": "unchanged"}, {"fault": "half_batch"},
                      {"variant": {"dtype": "bfloat16"}}],
    ("serve", "top_k"): [{"fault": "altered"}, {"fault": "half_batch"},
                         {"check_dtype": "float8_e4m3fn"}],
    ("serve", "predict"): [{"fault": "altered"}, {"fault": "half_batch"},
                           {"check_dtype": "float8_e4m3fn"}],
}


def bench() -> dict:
    b = copy.deepcopy(harness.load_benchmark())
    for cell in EXTRA.values():
        b["workloads"].append(dict(cell))
        for m in b["end_to_end"] + b["per_layer"]:
            if "serve_p95_ms" in (m["name"], m.get("moves")) \
                    and "workloads" in m:
                m["workloads"].append(cell["name"])
    return b


def mix_of(workload: str) -> dict:
    if workload == PREDICT["name"]:
        return copy.deepcopy(PREDICT_MIX)
    if workload == BURST["name"]:
        mix = harness.load_mix("serve_topk_open")
        mix["arrivals"] = dict(BURST_ARRIVALS)
        return mix
    return harness.load_mix(harness.workload_entry(bench(), workload)[
        "traffic"])


def kind_of(workload: str) -> tuple[str, str | None]:
    """The kind and query of the cell's mix, which key ``FAULTS``."""
    mix = mix_of(workload)
    return mix["kind"], mix.get("query")


def over_limits(out: dict) -> list[str]:
    """The compared numbers of a run that exceed their limits."""
    return [name for name, c in out["checks"].items()
            if c["limit"] is not None and not c["value"] <= c["limit"]]


def shrink(workload: str, cfg: dict | None = None) -> tuple[dict, dict]:
    """The cell's configuration (or ``cfg`` in its place) and mix, cut to
    a tiny tensor of the same order; ranks and core rank stay as given."""
    wl = harness.workload_entry(bench(), workload)
    cfg = (harness.load_config(wl["config"]) if cfg is None
           else copy.deepcopy(cfg))
    mix = mix_of(workload)
    order = len(cfg["dims"])
    cfg.update(dims=[300, 200, 40] if order == 3 else [40] * order,
               train_nnz=40_000, test_nnz=3_000, batch=256, rmse_target=0.5)
    mix.update(trace_seconds=0.2)
    if mix["kind"] == "serve":
        mix["arrivals"]["rate_per_s"] = 50
        if "on_s" in mix["arrivals"]:
            mix["arrivals"].update(on_s=0.1, off_s=0.1)
        mix.update(warm_sizes_upto=16)
    return cfg, mix


def run(workload: str, *, trace: bool = False, seconds: float = 0.4,
        seed: int = SEED, cfg: dict | None = None, **options) -> dict:
    cfg, mix = shrink(workload, cfg)
    if workload == PREDICT["name"]:
        options.setdefault("limits", PREDICT_LIMITS)
    if workload == BURST["name"]:
        options.setdefault("limits", harness.load_limits("yahoo.serve_topk"))
    return harness.run_cell(workload, seed, seconds, trace,
                            time.perf_counter(), bench=bench(),
                            peaks=CPU_PEAKS, cfg=cfg, mix=mix, **options)
