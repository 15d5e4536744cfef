import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import (device count locks at
# first init). Placeholder host devices exist ONLY for this dry-run, and
# they are CPU devices: the dry-run never touches an accelerator.

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, get_config       # noqa: E402
from repro.distributed import context as dist_ctx            # noqa: E402
from repro.distributed.sharding import (                     # noqa: E402
    batch_spec, cache_axes_tree, shardings_for_tree,
)
from repro.launch import hlo_analysis                        # noqa: E402
from repro.launch import steps as S                          # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.optim import adamw                                # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (and persists to JSON for §Roofline):
  * compiled.memory_analysis()  — proves the state/activations fit,
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes parsed from the post-SPMD HLO text, summed per op kind.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out artifacts/dryrun
"""

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_OPERAND_RE = re.compile(r"(bf16|f64|f32|f16|f8e4m3|f8e5m2|s64|s32|s16|s8|"
                         r"u64|u32|u16|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("//"):
            continue
        for kind in _COLLECTIVES:
            marker = f" {kind}("
            pos = stripped.find(marker)
            if pos < 0 or f"{kind}-start" in stripped.split("=")[0]:
                if pos < 0:
                    continue
            # operands are inside the call parens
            args = stripped[pos + len(marker):]
            depth = 1
            end = 0
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            args = args[:end]
            for m in _OPERAND_RE.finditer(args):
                out[kind] += _shape_bytes(m.group(1), m.group(2))
                out["count"] += 1
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _mem_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    keys = [
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes", "peak_memory_in_bytes",
    ]
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def _input_shardings(specs: dict, mesh, policy: str = "fsdp_tp") -> dict:
    out = {}
    for name, sds in specs.items():
        out[name] = NamedSharding(
            mesh, batch_spec(mesh, sds.shape[0],
                             extra_dims=len(sds.shape) - 1, policy=policy)
        )
    return out


def run_cell(arch: str, shape_name: str, mesh, policy: str,
             hlo_path: str | None = None, variant: str = "base") -> dict:
    import dataclasses
    cfg = get_config(arch)
    knobs = set(variant.split("+")) if variant != "base" else set()
    if "opt" in knobs:
        knobs |= {"absorb", "mp", "rk", "moe"}
    if knobs:
        cfg = dataclasses.replace(
            cfg,
            mla_absorb="absorb" in knobs,
            mixed_precision="mp" in knobs,
            repeat_kv="rk" in knobs,
            moe_sharded="moe" in knobs,
        )
    cell = SHAPES[shape_name]
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "policy": policy,
        "hlo_path": hlo_path,
        "variant": variant,
    }
    ok, reason = cfg.supports_shape(shape_name)
    if not ok:
        rec["status"] = reason
        return rec

    t0 = time.time()
    specs = S.input_specs(cfg, cell)
    in_sh = _input_shardings(specs, mesh, policy)
    repl = NamedSharding(mesh, P())

    # sequence-parallel residual constraint (train/prefill only); under
    # full-DP policies the batch covers every axis — no SP needed
    if cell.kind in ("train", "prefill") and policy != "zero3_dp":
        dist_ctx.set_activation_constraint(
            dist_ctx.make_seq_constraint(
                mesh, cell.global_batch, cell.seq_len, policy)
        )
    else:
        dist_ctx.set_activation_constraint(None)
    if policy != "zero3_dp":
        dist_ctx.set_logits_constraint(
            dist_ctx.make_logits_constraint(mesh, cell.global_batch,
                                            cfg.vocab_size))

    dist_ctx.set_mesh(mesh)
    with mesh:
        if cell.kind == "train":
            state_sh, state_axes = S.train_state_shapes(cfg)
            state_shardings = S.TrainState(
                shardings_for_tree(state_axes.params, state_sh.params, mesh,
                                   policy),
                adamw.AdamWState(
                    step=repl,
                    m=shardings_for_tree(state_axes.opt.m, state_sh.opt.m,
                                         mesh, policy),
                    v=shardings_for_tree(state_axes.opt.v, state_sh.opt.v,
                                         mesh, policy),
                ),
            )
            step = S.make_train_step(cfg, adamw.AdamWConfig())
            jitted = jax.jit(
                step,
                in_shardings=(state_shardings, in_sh),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state_sh, specs)
        elif cell.kind == "prefill" and cfg.encoder_only:
            params_sh, p_axes = S.model_shapes(cfg)
            p_shardings = shardings_for_tree(p_axes, params_sh, mesh, policy)
            step = S.make_encoder_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_shardings, in_sh))
            lowered = jitted.lower(params_sh, specs)
        elif cell.kind == "prefill":
            params_sh, p_axes = S.model_shapes(cfg)
            p_shardings = shardings_for_tree(p_axes, params_sh, mesh, policy)
            caches_sh = S.cache_shapes(cfg, cell.global_batch, cell.seq_len)
            c_axes = cache_axes_tree(caches_sh)
            c_shardings = shardings_for_tree(c_axes, caches_sh, mesh, policy)
            step = S.make_prefill_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, in_sh, c_shardings),
                out_shardings=(None, c_shardings),
                donate_argnums=(2,),
            )
            lowered = jitted.lower(params_sh, specs, caches_sh)
        else:  # decode
            params_sh, p_axes = S.model_shapes(cfg)
            p_shardings = shardings_for_tree(p_axes, params_sh, mesh, policy)
            caches_sh = S.cache_shapes(cfg, cell.global_batch, cell.seq_len)
            c_axes = cache_axes_tree(caches_sh)
            c_shardings = shardings_for_tree(c_axes, caches_sh, mesh, policy)
            step = S.make_decode_step(cfg)
            idx_sh = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, c_shardings, repl, in_sh),
                out_shardings=(None, c_shardings, repl),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_sh, caches_sh, idx_sh, specs)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    dist_ctx.set_activation_constraint(None)
    dist_ctx.set_logits_constraint(None)
    dist_ctx.set_mesh(None)
    cost = compiled.cost_analysis() or {}
    rec["xla_flops_noloop"] = float(cost.get("flops", -1))
    rec["xla_bytes_noloop"] = float(cost.get("bytes accessed", -1))
    rec["memory"] = _mem_dict(compiled)
    # persist the post-SPMD HLO (gzip) so §Roofline can be re-derived
    # without recompiling
    hlo_text = compiled.as_text()
    if rec.get("hlo_path"):
        import gzip
        with gzip.open(rec["hlo_path"], "wt") as f:
            f.write(hlo_text)
    # loop-aware per-partition accounting (scans multiplied by trip count)
    loopaware = hlo_analysis.analyze(hlo_text)
    rec["flops"] = loopaware["flops"]
    rec["transcendentals"] = loopaware["transcendentals"]
    rec["hbm_bytes"] = loopaware["hbm_bytes"]
    rec["collectives"] = {
        "operand": loopaware["collective_operand_bytes"],
        "wire": loopaware["collective_wire_bytes"],
        "total": loopaware["collective_operand_total"],
        "wire_total": loopaware["collective_wire_total"],
    }
    rec["status"] = "OK"
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--policy", default="fsdp_tp")
    ap.add_argument("--variant", default="base",
                    help="base | opt | knob list e.g. mp+rk "
                         "(absorb, mp, rk, moe)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_tag = "multi" if multi else "single"
        for arch in archs:
            for shape in shapes:
                tag = (f"{arch}.{shape}.{mesh_tag}.{args.policy}"
                       + ("" if args.variant == "base"
                          else f".{args.variant}"))
                path = outdir / f"{tag}.json"
                if path.exists() and not args.force:
                    rec = json.loads(path.read_text())
                    print(f"[cached] {tag}: {rec.get('status')}")
                    continue
                hlo_dir = outdir.parent / "hlo"
                hlo_dir.mkdir(parents=True, exist_ok=True)
                try:
                    rec = run_cell(arch, shape, mesh, args.policy,
                                   hlo_path=str(hlo_dir / f"{tag}.txt.gz"),
                                   variant=args.variant)
                except Exception as e:  # record the failure — it's a bug
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_tag,
                        "policy": args.policy, "status": "FAIL",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                path.write_text(json.dumps(rec, indent=1))
                mem = rec.get("memory", {}).get("temp_size_in_bytes", 0)
                print(
                    f"[{rec['status']:>4s}] {tag} "
                    f"flops={rec.get('flops', 0):.3g} "
                    f"coll={rec.get('collectives', {}).get('total', 0):.3g}B "
                    f"temp={mem/2**30:.2f}GiB "
                    f"(lower {rec.get('lower_s', 0)}s, "
                    f"compile {rec.get('compile_s', 0)}s)",
                    flush=True,
                )


if __name__ == "__main__":
    main()
