import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds abstract (ShapeDtypeStruct) state/batch trees
with their NamedShardings attached, lowers the jitted step, compiles it,
and records:

  - memory_analysis()  (per-device bytes: proves it fits)
  - cost_analysis()    (HLO FLOPs / bytes for §Roofline)
  - collective bytes   (parsed from the optimized HLO: all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute)

Results go to results/dryrun/<arch>__<shape>__<mesh>.json.  ``--all``
sweeps every supported cell in subprocesses (isolation: one cell's OOM or
crash cannot take down the sweep; XLA compilation memory is returned to
the OS between cells).

NOTE: the XLA_FLAGS line above MUST precede any jax import — jax locks
the device count at first init.  This module is the only place the
512-device fiction exists.
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (cells, get_config, input_specs, step_kind)
from ..configs.base import SHAPES, input_batch_axes
from ..distributed.sharding import (DEFAULT_RULES, activation_sharding,
                                    batch_sharding, param_sharding)
from ..models import model as M
from ..optim import adamw_init
from ..train.trainer import (TrainState, init_train_state, make_train_step,
                             shardings_for_state)
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DUMP_HLO = None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32"
                       r"|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape literal in ``text``."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.groups()
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind byte totals from optimized HLO.

    Counts the *output* shape of each collective op line (the data that
    crosses links, up to algorithm factors noted in EXPERIMENTS.md)."""
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # match '<shape> <op>(' with optional '%name = ' prefix
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
                      + r")[\s(-]", stripped)
        if not m:
            continue
        shape_txt, kind = m.groups()
        # fusions mentioning collectives in metadata don't match '= shape op('
        out[kind] += _shape_bytes(shape_txt)
        count[kind] += 1
    return {"bytes": out, "count": count,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------

def _abstract_state(cfg):
    """Abstract TrainState + axes pytree, zero allocation.

    The axes tree is plain Python built during tracing — capture it as a
    side effect of eval_shape."""
    captured = {}

    def build(key):
        state, axes = init_train_state(cfg, key)
        captured["axes"] = axes
        return state

    state_shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    return state_shapes, captured["axes"]


def _abstract_params(cfg):
    captured = {}

    def build(key):
        params, axes = M.init_model(cfg, key)
        captured["axes"] = axes
        return params

    params_shapes = jax.eval_shape(build, jax.random.PRNGKey(0))
    return params_shapes, captured["axes"]


GRAD_ACCUM = 8          # microbatch fold depth for train cells
MOE_PREFILL_CHUNK = 16384   # MoE token-chunking for serve paths


def abstract_train_cell(arch: str, shape: str, mesh, overrides=None):
    """(jitted train_step fn, abstract args) — no allocation."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    train_step = make_train_step(cfg, grad_accum=GRAD_ACCUM)
    state_shapes, axes = _abstract_state(cfg)
    state_sh = shardings_for_state(state_shapes, axes, mesh)
    state_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, state_sh)

    batch_spec = input_specs(arch, shape, cfg)
    batch_axes = input_batch_axes(arch, shape, cfg)
    batch_sh = batch_sharding(mesh, batch_spec, logical_tree=batch_axes)
    batch_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        batch_spec, batch_sh)

    def wrapped(s, b):
        with activation_sharding(mesh, DEFAULT_RULES):
            return train_step(s, b)

    fn = jax.jit(wrapped, out_shardings=(state_sh, NamedSharding(mesh, P())),
                 donate_argnums=(0,))
    return fn, (state_abs, batch_abs), cfg


def abstract_serve_cell(arch: str, shape: str, mesh, *, prefill: bool,
                        overrides=None):
    """Serve cells: prefill (full forward) or decode (one token + cache)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg.is_moe:
        cfg = _dc.replace(cfg, moe_token_chunk=MOE_PREFILL_CHUNK)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    spec = SHAPES[shape]
    b, s = spec["batch"], spec["seq"]
    params_shapes, axes = _abstract_params(cfg)
    p_sh = param_sharding(axes, mesh, params_shapes)
    params_abs = jax.tree.map(
        lambda sp, sh: jax.ShapeDtypeStruct(sp.shape, sp.dtype, sharding=sh),
        params_shapes, p_sh)

    if prefill:
        batch_spec = input_specs(arch, shape, cfg)
        batch_axes = input_batch_axes(arch, shape, cfg)
        batch_sh = batch_sharding(mesh, batch_spec, logical_tree=batch_axes)
        batch_abs = jax.tree.map(
            lambda sp, sh: jax.ShapeDtypeStruct(sp.shape, sp.dtype,
                                                sharding=sh),
            batch_spec, batch_sh)

        def prefill_step(params, batch):
            with activation_sharding(mesh, DEFAULT_RULES):
                logits, _ = M.forward(
                    params, cfg, batch.get("tokens"),
                    embeddings=batch.get("embeddings"),
                    mrope_positions=batch.get("mrope_positions"))
                return logits

        fn = jax.jit(prefill_step)
        return fn, (params_abs, batch_abs), cfg

    # decode: cache + one token
    cache_shapes = jax.eval_shape(partial(M.init_decode_state, cfg, b, s))
    cache_axes = M.decode_state_axes(cfg)
    rules = dict(DEFAULT_RULES, kv_seq="model")
    cache_sh = param_sharding(cache_axes, mesh, cache_shapes, rules)
    cache_abs = jax.tree.map(
        lambda sp, sh: jax.ShapeDtypeStruct(sp.shape, sp.dtype, sharding=sh),
        cache_shapes, cache_sh)
    tok_abs = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32,
        sharding=batch_sharding(mesh, {"t": jax.ShapeDtypeStruct(
            (b, 1), jnp.int32)})["t"])
    # synchronized batch decode: one shared position scalar (enables the
    # aliasing-friendly dynamic-update-slice cache write)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))

    def decode(params, cache, token, pos):
        with activation_sharding(mesh, rules):
            return M.decode_step(params, cfg, cache, token, pos)

    fn = jax.jit(decode, out_shardings=(NamedSharding(mesh, P()), cache_sh),
                 donate_argnums=(1,))
    return fn, (params_abs, cache_abs, tok_abs, pos_abs), cfg


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

def model_flops(cfg, shape_name: str) -> float:
    """MODEL_FLOPS: 6·N·D for training (N active params, D global tokens);
    2·N·D for inference (forward only).  Attention score flops excluded
    by convention (reported separately by the HLO analysis)."""
    spec = SHAPES[shape_name]
    kind = spec["kind"]
    n_active = cfg.params_active
    if kind == "train":
        tokens = spec["batch"] * spec["seq"]
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = spec["batch"] * spec["seq"]
        return 2.0 * n_active * tokens
    tokens = spec["batch"]  # one new token per sequence
    return 2.0 * n_active * tokens


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             overrides: dict | None = None, tag: str = "") -> dict:
    from .scan_registry import clear_registry, get_registry
    from .hlo_analysis import analyze

    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = step_kind(shape)
    clear_registry()
    t0 = time.time()
    if kind == "train":
        fn, args, cfg = abstract_train_cell(arch, shape, mesh, overrides)
    elif kind == "prefill":
        fn, args, cfg = abstract_serve_cell(arch, shape, mesh, prefill=True,
                                            overrides=overrides)
    else:
        fn, args, cfg = abstract_serve_cell(arch, shape, mesh,
                                            prefill=False,
                                            overrides=overrides)

    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if _DUMP_HLO:
        with open(_DUMP_HLO, "w") as f:
            f.write(hlo)
        import pickle
        with open(_DUMP_HLO + ".registry", "w") as f:
            json.dump(get_registry(), f)
    coll_naive = collective_bytes(hlo)
    corrected = analyze(hlo, get_registry(),
                        flash_model=getattr(cfg, "flash_model", False))

    n_chips = int(mesh.devices.size)
    raw_flops = float(cost.get("flops", 0.0))
    # cost_analysis is per-device but counts while bodies once; the
    # call-graph walk gives trip-count-corrected per-device dot flops.
    flops = max(corrected["dot_flops"], raw_flops)
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    bytes_accessed = max(corrected["bytes_accessed"], raw_bytes)
    wire = corrected["total_wire_bytes"]
    mflops = model_flops(cfg, shape)

    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "hlo_flops_raw": raw_flops,
        "hlo_flops": flops,
        "hlo_bytes_accessed_raw": raw_bytes,
        "hlo_bytes_accessed": bytes_accessed,
        "collectives_naive": coll_naive,
        "collectives": {
            "raw_bytes": corrected["collective_raw_bytes"],
            "wire_bytes": corrected["collective_wire_bytes"],
            "counts": corrected["collective_counts"],
            "total_wire_bytes": wire,
        },
        "unknown_whiles": corrected["unknown_whiles"],
        "scan_registry": corrected["registry"],
        "params_total": int(cfg.params_total),
        "params_active": int(cfg.params_active),
        "model_flops_global": mflops,
        "model_flops_per_chip": mflops / n_chips,
        "useful_flops_ratio": (mflops / n_chips) / max(flops, 1.0),
    }
    # All quantities are per-device (SPMD-partitioned HLO shard shapes):
    # wire bytes per chip / link bandwidth == the brief's
    # global_bytes / (chips × link_bw).
    result["roofline"] = {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_accessed / HBM_BW,
        "collective_s": wire / ICI_BW,
    }
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: result["roofline"][k])
    result["roofline"]["dominant"] = dom
    if overrides:
        result["overrides"] = {k: str(v) for k, v in overrides.items()}
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fname = f"{arch}__{shape}__{result['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--dump-hlo", help="write optimized HLO text here")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (perf experiments)")
    ap.add_argument("--tag", default="", help="result filename suffix")
    args = ap.parse_args()
    if args.dump_hlo:
        global _DUMP_HLO
        _DUMP_HLO = args.dump_hlo
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                if v in ("True", "False"):
                    v = v == "True"
        overrides[k] = v

    if args.all:
        jobs = []
        for arch, shape, ok, why in cells():
            for mp in (False, True):
                jobs.append((arch, shape, mp))
        failures = []
        for arch, shape, mp in jobs:
            mesh_tag = "2x16x16" if mp else "16x16"
            fname = os.path.join(args.out,
                                 f"{arch}__{shape}__{mesh_tag}.json")
            if args.skip_existing and os.path.exists(fname):
                print(f"SKIP {arch} {shape} {mesh_tag}", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            dt = time.time() - t0
            if r.returncode != 0:
                failures.append((arch, shape, mesh_tag))
                print(f"FAIL {arch} {shape} {mesh_tag} ({dt:.0f}s)\n"
                      f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}", flush=True)
            else:
                print(f"OK   {arch} {shape} {mesh_tag} ({dt:.0f}s)",
                      flush=True)
        print(f"\n{len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    res = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                   overrides or None, args.tag)
    print(json.dumps({k: res[k] for k in
                      ("arch", "shape", "mesh", "hlo_flops",
                       "useful_flops_ratio", "roofline")}, indent=1))
    print("memory_analysis:", res["memory"])
    print("collective wire bytes:", res["collectives"]["wire_bytes"])
    if res["unknown_whiles"]:
        print("WARNING unknown whiles:", res["unknown_whiles"])


if __name__ == "__main__":
    main()
