import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the full-size model (ShapeDtypeStruct stand-ins,
no allocation), attaches the production sharding rules, lowers and compiles
the train/prefill/decode step for the 16x16 single-pod mesh and the 2x16x16
multi-pod mesh, and records:

  * memory_analysis()      — per-device bytes (proves it fits)
  * cost_analysis()        — HLO flops / bytes (roofline numerator)
  * the collective schedule — every all-reduce/all-gather/reduce-scatter/
    all-to-all/collective-permute parsed from the optimized HLO with its
    payload bytes (roofline collective term)

Results go to experiments/dryrun/<arch>__<shape>__<mesh>[__tag].json and
are consumed by benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import gc
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import SHAPES_BY_NAME, replace
from repro.core import ema as ema_lib
from repro.distributed import sharding
from repro.launch.mesh import make_production_mesh, use_mesh
from repro.models import get_model, registry
from repro.optim import make_optimizer, schedules
from repro.optim.optimizers import rmsprop_momentum
from repro.train import serve_step as serve_lib
from repro.train import train_step as train_lib

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?P<sig>\([^)]*\)|\S+)\s+(?P<op>all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?P<suffix>-start|-done)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")
_COMP_RE = re.compile(r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\(.*\)\s*->.*\{")
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=%?(?P<cond>[\w.\-]+),\s*body=%?(?P<body>[\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(?P<n>\d+)"\}')


def _shape_bytes(sig: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(sig):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_DEF_RE = re.compile(r"^%?(?P<name>[\w.\-]+)\s+=\s+(?P<sig>\([^)]*\)|\S+)\s+\w")
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Collective payload bytes from optimized HLO, with while-loop bodies
    multiplied by their known_trip_count (XLA's cost_analysis counts loop
    bodies ONCE — verified in tests/test_spmd_subprocess.py — so a naive
    grep undercounts scanned-layer collectives by ~num_layers).

    Records both result and operand payloads: all-gather results exceed
    their operands, reduce-scatter operands exceed their results; the wire
    model in benchmarks.roofline uses max(result, operands) per op.
    """
    # 1. split into computations; build a name -> bytes symbol table
    comp_colls: Dict[str, list] = {}
    comp_whiles: Dict[str, list] = {}
    defs: Dict[str, int] = {}
    entry = None
    current = None
    pending: Dict[str, list] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not line.startswith(" "):
            m = _COMP_RE.match(stripped)
            if m:
                current = m.group("name")
                comp_colls.setdefault(current, [])
                comp_whiles.setdefault(current, [])
                pending.setdefault(current, [])
                if m.group("entry"):
                    entry = current
                continue
        if current is None:
            continue
        dm = _DEF_RE.match(stripped.removeprefix("ROOT ").strip())
        if dm:
            defs[dm.group("name")] = _shape_bytes(dm.group("sig"))
        wm = _WHILE_RE.search(stripped)
        if wm:
            tm = _TRIP_RE.search(stripped)
            trips = int(tm.group("n")) if tm else 1
            comp_whiles[current].append((wm.group("body"), wm.group("cond"), trips))
        cm = _COLL_RE.search(stripped)
        if cm and cm.group("suffix") != "-done":   # count start, not done
            res_bytes = _shape_bytes(cm.group("sig"))
            om = _OPERANDS_RE.search(stripped[cm.end() - 1:])
            operands = re.findall(r"%([\w.\-]+)", om.group(1)) if om else []
            pending[current].append((cm.group("op"), res_bytes, operands))

    # resolve operand byte sizes now that the symbol table is complete
    for comp, items in pending.items():
        for kind, res_bytes, operands in items:
            op_bytes = sum(defs.get(o, 0) for o in operands)
            comp_colls[comp].append((kind, res_bytes, op_bytes))

    # 2. resolve execution multiplicity from ENTRY through nested whiles
    mult: Dict[str, float] = {}

    def visit(name: str, m: float) -> None:
        mult[name] = mult.get(name, 0.0) + m
        for body, cond, trips in comp_whiles.get(name, []):
            visit(body, m * trips)
            visit(cond, m * (trips + 1))

    if entry:
        visit(entry, 1.0)

    # 3. aggregate
    per_kind: Dict[str, Dict[str, float]] = {}
    for comp, colls in comp_colls.items():
        m = mult.get(comp, 0.0)
        if m == 0.0 or not colls:
            continue
        for kind, res_bytes, op_bytes in colls:
            d = per_kind.setdefault(kind, {"count": 0.0, "bytes": 0.0,
                                           "wire_bytes": 0.0})
            d["count"] += m
            d["bytes"] += m * res_bytes
            d["wire_bytes"] += m * max(res_bytes, op_bytes)
    return {"per_kind": per_kind,
            "total_bytes": sum(d["bytes"] for d in per_kind.values()),
            "total_wire_bytes": sum(d["wire_bytes"]
                                    for d in per_kind.values()),
            "num_ops": sum(d["count"] for d in per_kind.values())}


def cost_analysis(compiled) -> Dict[str, Any]:
    """``compiled.cost_analysis()``, ``{}`` where XLA reports none."""
    return compiled.cost_analysis() or {}


def analyze(compiled, lower_s: float, compile_s: float) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    ca = cost_analysis(compiled)
    coll = parse_collectives(compiled.as_text())
    return {
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        },
        "cost": {
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
        },
        "collectives": coll,
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
    }


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------


def _mesh_and_cfg(multi_pod: bool):
    mesh = make_production_mesh(multi_pod=multi_pod)
    names = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = names.get("pod", 1) * names.get("data", 1)
    return mesh, dp


def model_config(arch: str, *, remat: Optional[str] = None,
                 moe_mode: Optional[str] = None):
    cfg = configs.get_config(arch)
    if remat:
        cfg = replace(cfg, remat=remat)
    if moe_mode and cfg.moe.enabled:
        cfg = replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                   partition_mode=moe_mode))
    return cfg


def train_policy(cfg, shape, mesh) -> Dict[str, Any]:
    """Auto-select the scale features needed for this cell to fit v5e HBM.

    * fsdp: shard params over data when the per-device TP shard > 2 GB
    * sp:   sequence-parallel activations for scan/attention families
    * microbatches: cap per-device saved-carry activations at ~1 GB
    """
    names = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = names.get("model", 1)
    dp = int(np.prod([v for k, v in names.items() if k in ("pod", "data")]))
    p = registry.param_count(cfg)
    param_gb = p * 2 / model_size / 1e9
    fsdp = param_gb > 2.0
    sp = (cfg.family in ("dense", "moe", "vlm", "audio")
          and shape.seq_len % model_size == 0)
    layers = cfg.num_layers + (cfg.num_encoder_layers
                               if cfg.family == "audio" else 0)
    local_batch = max(1, shape.global_batch // dp)
    act_bytes = (layers * local_batch * shape.seq_len * cfg.d_model * 2
                 / (model_size if sp else 1))
    # dense-attention scores [B_mb, H/model, S, S] f32 also scale 1/micro
    score_bytes = 0.0
    if cfg.family != "ssm" and shape.seq_len <= 8192:
        heads_local = max(1, cfg.num_heads // model_size)
        score_bytes = local_batch * heads_local * shape.seq_len ** 2 * 4
    micro = 1
    while (act_bytes + score_bytes) / micro > 5e8 and micro < local_batch:
        micro *= 2
    # EMA is an EVAL artifact (paper evaluates on \bar theta); for >20B
    # params the f32 shadow moves to the host checkpoint/eval path instead
    # of occupying HBM in the train step.
    ema_device = p <= 20e9
    return {"fsdp": fsdp, "sp": sp, "microbatches": micro,
            "ema_device": ema_device}


def lower_train(cfg, shape, mesh, num_workers: int, *, zero1: bool = True,
                ema: bool = True, donate: bool = True,
                policy: Optional[Dict[str, Any]] = None):
    from repro.distributed.context import sequence_parallel
    policy = policy if policy is not None else train_policy(cfg, shape, mesh)
    ema = ema and policy.get("ema_device", True)
    model = get_model(cfg)
    opt = rmsprop_momentum(schedules.constant(0.045 * num_workers))

    params_t = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_t = jax.eval_shape(opt.init, params_t)
    ema_t = jax.eval_shape(ema_lib.init, params_t) if ema else None
    specs = train_lib.input_specs(cfg, shape, num_workers=num_workers)

    p_sh = sharding.param_shardings(cfg, mesh, params_t,
                                    fsdp=policy.get("fsdp", False))
    g_sh = (sharding.grad_shardings(cfg, mesh, params_t)
            if policy.get("zero2", True) else None)
    o_sh = sharding.opt_state_shardings(cfg, mesh, opt_t, zero1=zero1)
    e_sh = sharding.opt_state_shardings(cfg, mesh, ema_t, zero1=zero1) if ema else None
    b_sh = sharding.batch_shardings(mesh, specs["batch"])
    scalar = sharding.batch_shardings(mesh, specs["step"])
    mask_sh = sharding.batch_shardings(mesh, specs["mask"])

    step_fn = train_lib.build_train_step(
        model, opt, num_workers=num_workers, n_aggregate=num_workers,
        ema_decay=0.9999 if ema else 0.0,
        num_microbatches=policy.get("microbatches", 1),
        grad_shardings=g_sh)

    in_sh = (p_sh, o_sh, e_sh, scalar, b_sh, mask_sh)
    out_sh = (p_sh, o_sh, e_sh, None)
    jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1, 2) if donate else ())
    from repro.distributed.context import (layer_param_constraints,
                                           moe_data_sharding)
    constrainer = (sharding.layer_param_constrainer(
        cfg, mesh, fsdp=policy.get("fsdp", False))
        if policy.get("layer_constraints", True) else None)
    with use_mesh(mesh), sequence_parallel(policy.get("sp", False)), \
            layer_param_constraints(constrainer), moe_data_sharding(True):
        return jitted.lower(params_t, opt_t, ema_t, specs["step"],
                            specs["batch"], specs["mask"])


def _serve_fsdp(cfg, mesh) -> bool:
    """Weight-gather-per-layer (ZeRO-inference) when the TP shard alone
    exceeds ~2 GB/device (command-r-plus: kv=8 caps useful TP at 16)."""
    names = dict(zip(mesh.axis_names, mesh.devices.shape))
    return registry.param_count(cfg) * 2 / names.get("model", 1) > 2e9


def lower_prefill(cfg, shape, mesh):
    from repro.distributed.context import moe_data_sharding, sequence_parallel
    model = get_model(cfg)
    params_t = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = serve_lib.prefill_input_specs(cfg, shape)
    p_sh = sharding.param_shardings(cfg, mesh, params_t,
                                    fsdp=_serve_fsdp(cfg, mesh))
    b_sh = sharding.batch_shardings(mesh, specs["batch"])
    fn = serve_lib.build_prefill(model)
    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
    # NOTE: no sequence-parallel here — SP pays off for remat-SAVED
    # activations in training; forward-only prefill frees each layer's
    # activations, and an S-sharded residual conflicts with the chunked
    # attention layout (GSPMD falls back to replication).
    with use_mesh(mesh), moe_data_sharding(True):
        return jitted.lower(params_t, specs["batch"])


def lower_decode(cfg, shape, mesh, cache_dtype=None):
    model = get_model(cfg)
    params_t = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = serve_lib.decode_input_specs(model, cfg, shape,
                                         cache_dtype=cache_dtype)
    p_sh = sharding.param_shardings(cfg, mesh, params_t,
                                    fsdp=_serve_fsdp(cfg, mesh))
    c_sh = sharding.cache_shardings(cfg, mesh, specs["cache"])
    t_sh = sharding.batch_shardings(mesh, {"t": specs["token"]})["t"]
    fn = serve_lib.build_decode_step(model)
    jitted = jax.jit(fn, in_shardings=(p_sh, t_sh, c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(2,))
    with use_mesh(mesh):
        return jitted.lower(params_t, specs["token"], specs["cache"])


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             zero1: bool = True, remat: Optional[str] = None,
             moe_mode: Optional[str] = None, tag: str = "",
             policy_override: Optional[Dict[str, Any]] = None,
             out_dir: str = OUT_DIR) -> Dict[str, Any]:
    mesh_name = "multi" if multi_pod else "single"
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    shape = SHAPES_BY_NAME[shape_name]
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    if configs.cell_is_skipped(arch, shape_name):
        result["status"] = "skipped"
        result["reason"] = ("pure full-attention arch; long_500k requires "
                            "sub-quadratic attention (DESIGN.md)")
        _save(out_dir, cell_id, result)
        return result

    cfg = model_config(arch, remat=remat, moe_mode=moe_mode)
    mesh, dp = _mesh_and_cfg(multi_pod)
    result["devices"] = int(np.prod(mesh.devices.shape))
    result["params"] = registry.param_count(cfg)
    result["active_params"] = registry.param_count(cfg, active_only=True)
    t0 = time.time()
    try:
        if shape.kind == "train":
            policy = dict(train_policy(cfg, shape, mesh), **(policy_override or {}))
            result["policy"] = {**policy, "zero1": zero1}
            lowered = lower_train(cfg, shape, mesh, dp, zero1=zero1,
                                  policy=policy)
        elif shape.kind == "prefill":
            lowered = lower_prefill(cfg, shape, mesh)
        else:
            cache_dtype = jnp.int8 if (policy_override or {}).get("cache_int8") \
                else None
            result["policy"] = {"cache_int8": cache_dtype is not None}
            lowered = lower_decode(cfg, shape, mesh, cache_dtype=cache_dtype)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        result.update(analyze(compiled, t1 - t0, t2 - t1))
        result["status"] = "ok"
        print(f"[dryrun] {cell_id}: OK "
              f"(lower {t1-t0:.1f}s compile {t2-t1:.1f}s "
              f"flops={result['cost']['flops']:.3e} "
              f"coll={result['collectives']['total_bytes']:.3e}B)")
        del compiled, lowered
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cell_id}: FAIL {type(e).__name__}: {e}")
    gc.collect()
    _save(out_dir, cell_id, result)
    return result


def _save(out_dir: str, cell_id: str, result: Dict[str, Any]) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(result, f, indent=2, default=float)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=list(SHAPES_BY_NAME))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-zero1", action="store_true",
                    help="ablation: replicated optimizer state")
    ap.add_argument("--remat", choices=["none", "full", "dots"])
    ap.add_argument("--moe-mode", choices=["tp", "ep"])
    ap.add_argument("--microbatch", type=int, help="override auto microbatches")
    ap.add_argument("--fsdp", choices=["on", "off"], help="override auto FSDP")
    ap.add_argument("--sp", choices=["on", "off"],
                    help="override sequence-parallel activations")
    ap.add_argument("--no-zero2", action="store_true",
                    help="ablation: all-reduce grads instead of reduce-scatter")
    ap.add_argument("--cache-int8", action="store_true",
                    help="decode shapes: int8-quantized KV cache")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in configs.list_archs():
            for shape in SHAPES_BY_NAME:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape required unless --all")
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            cid = f"{arch}__{shape}__{'multi' if mp else 'single'}" + \
                (f"__{args.tag}" if args.tag else "")
            path = os.path.join(args.out, cid + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue
            override: Dict[str, Any] = {}
            if args.microbatch:
                override["microbatches"] = args.microbatch
            if args.fsdp:
                override["fsdp"] = args.fsdp == "on"
            if args.sp:
                override["sp"] = args.sp == "on"
            if args.no_zero2:
                override["zero2"] = False
            if args.cache_int8:
                override["cache_int8"] = True
            r = run_cell(arch, shape, mp, zero1=not args.no_zero1,
                         remat=args.remat, moe_mode=args.moe_mode,
                         tag=args.tag, policy_override=override or None,
                         out_dir=args.out)
            failures += r["status"] == "error"
    print(f"[dryrun] done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
