import os
# A CPU-only compile tool: pin the CPU backend (this process and the per-cell
# children, which inherit the environment, can never take an accelerator)
# and fake 512 host devices.  MUST precede every other import (jax locks the
# backend and the device count on first init).
os.environ["JAX_PLATFORMS"] = "cpu"
_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"
if _DEVICES_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), _DEVICES_FLAG) if f)

DOC = """Multi-pod dry-run: AOT-lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step with optimizer,
or the serving prefill/decode step), attaches NamedShardings to
ShapeDtypeStruct stand-ins (zero allocation), runs ``.lower().compile()``
against the 256-chip single-pod / 512-chip two-pod mesh, and records:

  * memory_analysis()  — per-device argument/output/temp/code bytes,
  * cost_analysis()    — HLO FLOPs + bytes accessed,
  * the collective schedule (parsed from post-SPMD HLO) with wire bytes.

Results land in experiments/dryrun/<arch>__<shape>__<mesh>[__variant].json;
benchmarks/roofline.py turns them into the §Roofline table.

Variants are the §Perf levers:
  --params-dtype bf16      (vs paper-faithful f32 master)
  --wq                     int8 weight-only serving (Pallas wq_matmul path)
  --qkv                    int8 KV cache (paper grid) for decode
  --remat {full,dots,none,off}
  --microbatch N           gradient-accumulation split
  --seq-shard              sequence-parallel activations
  --no-decode-kv-shard     replicate the KV cache instead of model-sharding it
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES
from repro.core.integerize import integerize_weights_only
from repro.dist import sharding as shd
from repro.launch import analysis
from repro.launch.mesh import HW, make_production_mesh
from repro.models.registry import get_config, list_archs
from repro.optim import sgd
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.trainer import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _cast_float(tree, dtype):
    def leaf(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(x.shape, dtype) \
                if isinstance(x, jax.ShapeDtypeStruct) else x.astype(dtype)
        return x
    return jax.tree_util.tree_map(leaf, tree)


def _sds_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def probe_cfg(cfg, k: int):
    """Depth-k probe: identical per-layer shapes, k periods, unrolled."""
    import dataclasses

    repl = {"arch_id": f"{cfg.arch_id}-probe{k}",
            "n_layers": cfg.first_k_dense + k * len(cfg.layout)}
    if cfg.is_encdec:
        repl["enc_layers"] = k
    return dataclasses.replace(cfg, **repl)


def lower_cell(cfg, shape_name: str, mesh, opts, *, scan_layers: bool = True):
    """Build the cell's step fn + sharded SDS args and AOT-lower it."""
    sh = SHAPES[shape_name]
    rules = shd.make_axis_rules(mesh, seq_shard=opts.seq_shard,
                                decode_kv_shard=not opts.no_decode_kv_shard,
                                dp_only=opts.dp_only)
    model = cfg.build(dtype=jnp.bfloat16, remat=opts.remat,
                      scan_layers=scan_layers)

    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))

    if sh.kind == "train":
        if opts.params_dtype != "float32":
            params_sds = _cast_float(params_sds, jnp.dtype(opts.params_dtype))
        optimizer = sgd(momentum=0.9, weight_decay=5e-4)
        opt_sds = jax.eval_shape(optimizer.init, params_sds)
        state_sds = {"params": params_sds, "opt": opt_sds,
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
        pspecs = shd.param_pspecs(params_sds, mesh, rules)
        state_sh = {"params": pspecs,
                    "opt": {"m": shd.param_pspecs(opt_sds["m"], mesh, rules)},
                    "step": shd.named(mesh)}
        batch_sds = cfg.input_specs(shape_name)
        batch_sh = shd.batch_pspecs(batch_sds, mesh, rules)
        step = make_train_step(model, optimizer, 0.01, mesh=mesh,
                               axis_rules=rules,
                               microbatch_split=opts.microbatch,
                               int8_weight_gather=getattr(opts, "wq_train",
                                                          False))
        args = (shd.with_shardings(state_sds, state_sh),
                shd.with_shardings(batch_sds, batch_sh))
        return jax.jit(step, donate_argnums=(0,)).lower(*args)
    else:
        # serving: bf16 weights baseline; --wq = int8 weight-only QTensors
        if opts.wq:
            params_sds = jax.eval_shape(
                lambda: integerize_weights_only(model.init(jax.random.PRNGKey(0))))
        else:
            params_sds = _cast_float(params_sds, jnp.bfloat16)
        pspecs = shd.param_pspecs(params_sds, mesh, rules,
                                  serve=(sh.kind == "decode"))
        specs = cfg.input_specs(shape_name)
        if sh.kind == "prefill":
            cache_sds = jax.eval_shape(lambda: model.init_cache(
                sh.global_batch, sh.seq_len, quantized_kv=opts.qkv,
                kv_dtype=jnp.bfloat16))
            cache_sh = shd.cache_pspecs(cache_sds, mesh, rules)
            tokens = specs["tokens"]
            tok_sh = shd.batch_pspecs(tokens, mesh, rules)
            step = make_prefill_step(model, mesh=mesh, axis_rules=rules)
            args = [shd.with_shardings(params_sds, pspecs),
                    shd.with_shardings(tokens, tok_sh),
                    shd.with_shardings(cache_sds, cache_sh)]
            kw = {}
            if "embeds" in specs:
                emb_sh = shd.batch_pspecs(specs["embeds"], mesh, rules)
                key = "enc" if cfg.is_encdec else "embeds"
                kw[key] = shd.with_shardings(specs["embeds"], emb_sh)
            return jax.jit(step, donate_argnums=(2,)).lower(*args, **kw)
        else:  # decode
            # build the cache from THIS model (scan vs unrolled probe layouts
            # differ; specs["cache"] assumes the scanned layout)
            cache_sds = jax.eval_shape(lambda: model.init_cache(
                sh.global_batch, sh.seq_len, quantized_kv=opts.qkv,
                kv_dtype=jnp.bfloat16))
            cache_sh = shd.cache_pspecs(cache_sds, mesh, rules)
            tok_sh = shd.batch_pspecs(specs["tokens"], mesh, rules)
            rng_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            step = make_decode_step(model, mesh=mesh, axis_rules=rules)
            args = [shd.with_shardings(params_sds, pspecs),
                    shd.with_shardings(specs["tokens"], tok_sh),
                    shd.with_shardings(cache_sds, cache_sh),
                    rng_sds]
            kw = {}
            if "enc" in specs:
                enc_sh = shd.batch_pspecs(specs["enc"], mesh, rules)
                kw["enc"] = shd.with_shardings(specs["enc"], enc_sh)
            return jax.jit(step, donate_argnums=(2,)).lower(*args, **kw)


def _compile_and_analyze(lowered):
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = analysis.memory_stats(compiled)
    cost = analysis.cost_stats(compiled)
    hlo = compiled.as_text()
    coll = analysis.parse_collectives(hlo)
    return {"memory": mem, "cost": cost, "collectives": coll,
            "collective_wire_bytes": analysis.total_wire_bytes(coll),
            "hlo_bytes": len(hlo), "compile_s": round(t_compile, 2)}


def build_cell(arch: str, shape_name: str, mesh, opts) -> dict:
    """Lower + compile one cell (full scanned model + 2 unrolled depth probes).

    XLA's cost_analysis counts a while-loop body ONCE, so the scanned stack's
    FLOPs/bytes/collectives are under-reported by ~n_periods.  The two probes
    (1 and 2 periods, unrolled) give exact per-period deltas:
        total(N) = probe1 + (N - 1) × (probe2 - probe1)
    Memory analysis comes from the full scanned compile (the real artifact).
    """
    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    n_chips = mesh.devices.size

    t0 = time.time()
    lowered = lower_cell(cfg, shape_name, mesh, opts, scan_layers=True)
    t_lower = time.time() - t0
    full = _compile_and_analyze(lowered)

    n_periods = (cfg.n_layers - cfg.first_k_dense) // len(cfg.layout)
    probes = {}
    extrap = {}
    if opts.probe and n_periods > 1:
        for k in (1, 2):
            pl = lower_cell(probe_cfg(cfg, k), shape_name, mesh, opts,
                            scan_layers=False)
            pr = _compile_and_analyze(pl)
            probes[k] = {"cost": pr["cost"],
                         "collective_wire_bytes": pr["collective_wire_bytes"],
                         "collectives": pr["collectives"],
                         "compile_s": pr["compile_s"]}

        def lin(v1, v2):
            return v1 + (n_periods - 1) * (v2 - v1)

        for key in ("flops", "bytes accessed"):
            v1 = probes[1]["cost"].get(key, 0.0)
            v2 = probes[2]["cost"].get(key, 0.0)
            extrap[key] = lin(v1, v2)
        extrap["wire_bytes"] = lin(probes[1]["collective_wire_bytes"],
                                   probes[2]["collective_wire_bytes"])
        extrap["n_periods"] = n_periods

    record = {
        "arch": arch, "shape": shape_name, "kind": sh.kind,
        "mesh": {"shape": dict(mesh.shape), "n_chips": int(n_chips)},
        "variant": opts.variant_name(),
        "seq_len": sh.seq_len, "global_batch": sh.global_batch,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "memory": full["memory"], "cost": full["cost"],
        "collectives": full["collectives"],
        "collective_wire_bytes": full["collective_wire_bytes"],
        "probes": probes, "extrapolated": extrap,
        "hlo_bytes": full["hlo_bytes"],
        "lower_s": round(t_lower, 2), "compile_s": full["compile_s"],
        "hw": HW,
    }
    return record


def cell_path(arch, shape_name, multi_pod, variant, out_dir):
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    v = f"__{variant}" if variant and variant != "baseline" else ""
    return os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_tag}{v}.json")


class Opts(argparse.Namespace):
    def variant_name(self):
        parts = []
        if self.params_dtype != "float32":
            parts.append(self.params_dtype)
        if self.wq:
            parts.append("wq")
        if getattr(self, "wq_train", False):
            parts.append("wqt")
        if self.qkv:
            parts.append("qkv")
        if self.remat != "full":
            parts.append(f"remat-{self.remat}")
        if self.microbatch != 1:
            parts.append(f"mb{self.microbatch}")
        if self.seq_shard:
            parts.append("sp")
        if self.dp_only:
            parts.append("dponly")
        if self.no_decode_kv_shard:
            parts.append("nokvs")
        return "-".join(parts) or "baseline"


def all_cells():
    for arch in list_archs():
        cfg = get_config(arch)
        for shape_name in SHAPES:
            if cfg.supports(shape_name):
                yield arch, shape_name


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every supported cell (subprocess per cell)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    # variants
    ap.add_argument("--params-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--wq", action="store_true")
    ap.add_argument("--wq-train", action="store_true",
                    help="int8 weight-gather training (STE, f32 master)")
    ap.add_argument("--qkv", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none", "off"])
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--dp-only", action="store_true")
    ap.add_argument("--no-decode-kv-shard", action="store_true")
    ap.add_argument("--no-probe", dest="probe", action="store_false",
                    help="skip the depth-probe compiles (cost extrapolation)")
    ap.add_argument("--timeout", type=int, default=3600)
    opts = ap.parse_args(argv, namespace=Opts())
    os.makedirs(opts.out, exist_ok=True)

    if opts.all:
        cells = list(all_cells())
        meshes = [False, True] if opts.both_meshes else [opts.multi_pod]
        failures = []
        for arch, shape_name in cells:
            for mp in meshes:
                path = cell_path(arch, shape_name, mp, opts.variant_name(),
                                 opts.out)
                if opts.skip_existing and os.path.exists(path):
                    print(f"skip {path}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--out", opts.out]
                if mp:
                    cmd.append("--multi-pod")
                for flag in ("wq", "wq_train", "qkv", "seq_shard", "dp_only",
                             "no_decode_kv_shard"):
                    if getattr(opts, flag):
                        cmd.append("--" + flag.replace("_", "-"))
                if opts.params_dtype != "float32":
                    cmd += ["--params-dtype", opts.params_dtype]
                if opts.remat != "full":
                    cmd += ["--remat", opts.remat]
                if opts.microbatch != 1:
                    cmd += ["--microbatch", str(opts.microbatch)]
                print(">>", " ".join(cmd), flush=True)
                r = subprocess.run(cmd, timeout=opts.timeout)
                if r.returncode != 0:
                    failures.append((arch, shape_name, mp))
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    assert opts.arch and opts.shape, "--arch and --shape required (or --all)"
    mesh = make_production_mesh(multi_pod=opts.multi_pod)
    path = cell_path(opts.arch, opts.shape, opts.multi_pod,
                     opts.variant_name(), opts.out)
    try:
        record = build_cell(opts.arch, opts.shape, mesh, opts)
    except Exception:
        record = {"arch": opts.arch, "shape": opts.shape,
                  "variant": opts.variant_name(),
                  "mesh": {"multi_pod": opts.multi_pod},
                  "error": traceback.format_exc()}
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        print(record["error"], file=sys.stderr)
        sys.exit(1)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    mb = record["memory"].get("argument_size_in_bytes", 0) / 2**20
    ex = record.get("extrapolated", {})
    print(f"OK {path}\n   args/device={mb:.1f}MiB "
          f"temp/device={record['memory'].get('temp_size_in_bytes', 0)/2**20:.1f}MiB "
          f"flops={ex.get('flops', record['cost'].get('flops', 0)):.3e} "
          f"wire={ex.get('wire_bytes', record['collective_wire_bytes']):.3e}B "
          f"compile={record['compile_s']}s")


if __name__ == "__main__":
    main()
