"""Fig. 7b/c + Fig. 8 analogue: multi-device STD scaling, via the registry.

Fake host devices share the same CPU cores, so wall-clock 'speedup' is not
observable here; what IS measurable and scale-relevant comes from the
compiled HLO of each strategy's actual distributed step, per device count
M ∈ {2, 4}:

  * per-device FLOPs per update step — ∝ 1/M (the work really divides);
  * per-step collective wire bytes — sync psums dense factor gradients
    (∝ model size), the strata flavors move factor shards (ppermute,
    independent of M); ``strata_overlap`` keeps shards rotated between
    strata so it moves STRICTLY fewer bytes per step than ``strata``;
  * communication/compute overlap evidence (``hlo_analysis.overlap_stats``):
    async collective-start count plus the dot-flops window between each
    rotation's issue point and its first consumer — the double-buffered
    ``strata_overlap`` step issues every rotation ahead of compute that
    doesn't depend on it.

Sweeps every strategy registered in ``repro.distributed``.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

from .common import row

REPO = Path(__file__).resolve().parent.parent

_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={M}"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import jax
import numpy as np
from repro.core import FastTuckerConfig, init_state
from repro.data.synthetic import planted_tensor
from repro.distributed import available_strategies, get_strategy
from repro.launch.mesh import make_host_mesh
from repro.launch.hlo_analysis import analyze, overlap_stats

dims = (1024, 768, 512)
t = planted_tensor(dims, 100_000, seed=0)
# strong scaling: fixed GLOBAL |Psi|=8192 split across devices
cfg = FastTuckerConfig(dims=dims, ranks=(8,)*3, core_rank=8,
                       batch_size=8192 // {M})
mesh = make_host_mesh()
out = {{}}
for name in available_strategies():
    st = get_strategy(name)
    plan = st.prepare(t, cfg, mesh if st.needs_mesh else None, seed=0)
    ds = st.init(plan, init_state(jax.random.PRNGKey(0), cfg),
                 jax.random.PRNGKey(1))
    with mesh:
        comp = st.lower_step(plan, ds).compile()
    txt = comp.as_text()
    a = analyze(txt)
    o = overlap_stats(txt)
    spc = st.steps_per_call(plan)
    out[name] = {{
        "flops": a["flops"] / spc,
        "coll": a["collective_wire_total"] / spc,
        "permutes": o["collective_permutes"] / spc,
        "hidden_flops": o["hidden_flops"] / spc,
        "async_starts": o["async_collective_starts"],
    }}
print(json.dumps(out))
"""


def _run_for(M: int) -> dict:
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={M}"
    env["JAX_PLATFORMS"] = "cpu"  # fake host devices; leave chips alone
    proc = subprocess.run(
        [sys.executable, "-c", _SNIPPET.format(M=M)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run() -> list[str]:
    out = []
    base_flops: dict[str, float] = {}
    for M in (2, 4):
        try:
            r = _run_for(M)
        except Exception as e:  # noqa: BLE001
            out.append(row(f"fig7bc/M{M}", 0.0, f"error={e}"))
            continue
        for name, s in sorted(r.items()):
            fl, cl = s["flops"], s["coll"]
            base_flops.setdefault(name, fl * M)
            eff = base_flops[name] / (fl * M)
            extras = (f"flops/dev={fl:.3g};coll/step={cl:.3g}B;"
                      f"work_scaling_eff={eff:.2f}")
            if name.startswith("strata"):
                extras += (f";permutes/step={s['permutes']:.2f};"
                           f"hidden_flops/step={s['hidden_flops']:.3g};"
                           f"async_starts={s['async_starts']}")
            out.append(row(f"fig7bc/{name}_M{M}", 0.0, extras))
        # the headline: overlapped strata must not move more bytes than
        # plain strata, while exposing a hiding window
        if "strata" in r and "strata_overlap" in r:
            ok = r["strata_overlap"]["coll"] <= r["strata"]["coll"] + 1e-6
            hid = (r["strata_overlap"]["hidden_flops"] > 0
                   or r["strata_overlap"]["async_starts"] > 0)
            out.append(row(
                f"fig7bc/overlap_check_M{M}", 0.0,
                f"coll_no_worse={ok};rotation_hidden={hid}"))
    return out
