"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
host devices stand in for 2 pods × 256 chips; every cell must lower and
compile under its production shardings, and the compiled artifact yields the
memory analysis (fits?) and cost analysis (FLOPs/bytes) plus the parsed
collective bytes that feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod ...
Outputs JSON per cell under experiments/dryrun/.
"""

# MUST precede any jax-touching import: device count locks on first init.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS",
                           "--xla_force_host_platform_device_count=512"))

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, PAPER_ARCH, SHAPES, get_config, shape_applicable
from repro.data.pipeline import make_batch_specs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.models.layers import Ctx
from repro.optim.adamw import adamw
from repro.runtime import sharding as shd
from repro.training import steps

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, parsed from the HLO result
    shapes (post-SPMD shapes are per-device)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        shapes_blob, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(shapes_blob):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + nbytes
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _with_shardings(shapes, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def make_ctx(cfg, mesh, global_batch, *, mode, opt=()):
    return Ctx(mode=mode, impl="xla", group_size=cfg.group_size,
               act_dtype="bfloat16",
               moe_token_chunk=32768 if cfg.n_experts else 0,
               kv_quant="kv8" in opt,
               qat_int8_fwd="int8fwd" in opt,
               remat_policy="dots" if "rematdots" in opt else "nothing",
               constrain=shd.make_constrain(
                   mesh, cfg, global_batch,
                   layout="dp" if ("dp" in opt or "dpzero1" in opt) else "2d"))


def build_cell(arch: str, shape_name: str, mesh, opt=()):
    """Returns (fn, arg_specs:list, donate:tuple) for one cell.

    ``opt``: hillclimb variants — subset of {"kv8", "dp", "rematdots",
    "compress"} (§Perf); empty = paper-faithful baseline layout.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    fsdp = cfg.d_model >= shd.FSDP_THRESHOLD
    layout = "dp" if "dp" in opt else "2d"
    key = jax.random.PRNGKey(0)

    params_shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, key, dtype=jnp.bfloat16))

    if shape.kind == "train":
        ctx = make_ctx(cfg, mesh, shape.global_batch, mode="qat", opt=opt)
        optimizer = adamw()
        opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
        batch_shapes = make_batch_specs(cfg, shape.global_batch,
                                        shape.seq_len)
        if "dpzero1" in opt:
            # DP layout via pjit: params replicated, batch over the whole
            # mesh, optimizer state ZeRO-1-sharded (small archs, cell B)
            rep = jax.tree_util.tree_map(
                lambda s: shd.ns(mesh), params_shapes,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            o_sh = type(opt_shapes)(
                step=shd.ns(mesh),
                m=shd.shard_opt_state_zero1(mesh, opt_shapes.m),
                v=shd.shard_opt_state_zero1(mesh, opt_shapes.v))
            dp_ax = shd._fit(mesh, shape.global_batch, shd.all_axes(mesh),
                             shd.batch_axes(mesh), "data")
            b_sh = jax.tree_util.tree_map(
                lambda s: shd.ns(mesh, *((dp_ax,) + (None,) * (s.ndim - 1))),
                batch_shapes,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            args = (_with_shardings(params_shapes, rep),
                    _with_shardings(opt_shapes, o_sh),
                    _with_shardings(batch_shapes, b_sh))
            fn = steps.make_train_step(cfg, ctx, optimizer)
            return fn, args, (0, 1)
        if layout == "dp":
            # pure-DP (optionally compressed) shard_map step
            err_shapes = jax.eval_shape(
                lambda p: jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), p),
                params_shapes)
            rep = lambda tree: jax.tree_util.tree_map(
                lambda s: shd.ns(mesh), tree,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            b_sh = jax.tree_util.tree_map(
                lambda s: shd.ns(mesh, *( (shd.all_axes(mesh),)
                                          + (None,) * (s.ndim - 1))),
                batch_shapes,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            args = (_with_shardings(params_shapes, rep(params_shapes)),
                    _with_shardings(opt_shapes, rep(opt_shapes)),
                    _with_shardings(err_shapes, rep(err_shapes)),
                    _with_shardings(batch_shapes, b_sh))
            fn = steps.make_train_step_ddp(cfg, ctx, optimizer, mesh,
                                           compress="compress" in opt)
            return fn, args, (0, 1, 2)
        p_sh = shd.shard_params(mesh, params_shapes, fsdp=fsdp)
        o_sh = jax.tree_util.tree_map(
            lambda s: shd.ns(mesh) if s.ndim == 0 else None, opt_shapes)
        # m/v mirror the param shardings leaf-for-leaf (ZeRO-consistent)
        o_sh = type(opt_shapes)(step=shd.ns(mesh), m=p_sh, v=p_sh)
        b_sh = jax.tree_util.tree_map(
            lambda s: shd.ns(mesh, *shd.batch_spec(
                mesh, shape.global_batch, s.ndim - 1)), batch_shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        args = (_with_shardings(params_shapes, p_sh),
                _with_shardings(opt_shapes, o_sh),
                _with_shardings(batch_shapes, b_sh))
        # Gradient accumulation: big archs trade steps for activation memory
        # (standard production practice, recorded per cell in the output).
        if cfg.d_model >= 8192:
            microbatches = 16
        elif cfg.n_experts:
            microbatches = 8
        elif cfg.d_model >= shd.FSDP_THRESHOLD or cfg.n_layers >= 32 \
                or cfg.block_kind == "hymba":
            microbatches = 4
        else:
            microbatches = 1
        fn = steps.make_train_step(cfg, ctx, optimizer,
                                   microbatches=microbatches)
        return fn, args, (0, 1)

    # serving cells use packed (integer TLMM) parameters
    packed_shapes = jax.eval_shape(
        lambda p: transformer.pack_params(cfg, p), params_shapes)
    p_sh = shd.shard_params(mesh, packed_shapes, fsdp=False)
    gb = shape.global_batch
    ctx = make_ctx(cfg, mesh, gb, mode="packed", opt=opt)
    kvq = "kv8" in opt

    if shape.kind == "prefill":
        cache_shapes = jax.eval_shape(
            lambda: transformer.init_cache(cfg, gb, shape.seq_len,
                                           jnp.bfloat16, kv_quant=kvq))
        c_sh = shd.cache_sharding(mesh, cache_shapes, gb)
        if cfg.frontend == "token":
            inp = jax.ShapeDtypeStruct((gb, shape.seq_len), jnp.int32)
        else:
            inp = jax.ShapeDtypeStruct((gb, shape.seq_len, cfg.d_model),
                                       jnp.bfloat16)
        inp = jax.ShapeDtypeStruct(
            inp.shape, inp.dtype,
            sharding=shd.ns(mesh, *shd.batch_spec(mesh, gb, inp.ndim - 1)))
        args = (_with_shardings(packed_shapes, p_sh), inp,
                _with_shardings(cache_shapes, c_sh))
        fn = steps.make_prefill_fn(cfg, ctx)
        return fn, args, (2,)

    # decode / long_decode: one new token against a seq_len cache
    cache_shapes = jax.eval_shape(
        lambda: transformer.init_cache(cfg, gb, shape.seq_len, jnp.bfloat16,
                                       kv_quant=kvq))
    c_sh = shd.cache_sharding(mesh, cache_shapes, gb)
    if cfg.frontend == "token":
        inp = jax.ShapeDtypeStruct((gb, 1), jnp.int32)
    else:
        inp = jax.ShapeDtypeStruct((gb, 1, cfg.d_model), jnp.bfloat16)
    inp = jax.ShapeDtypeStruct(
        inp.shape, inp.dtype,
        sharding=shd.ns(mesh, *shd.batch_spec(mesh, gb, inp.ndim - 1)))
    clen = jax.ShapeDtypeStruct((), jnp.int32, sharding=shd.ns(mesh))
    args = (_with_shardings(packed_shapes, p_sh), inp,
            _with_shardings(cache_shapes, c_sh), clen)
    fn = steps.make_decode_fn(cfg, ctx)
    return fn, args, (2,)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = "experiments/dryrun", opt=()) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}_{shape_name}_{mesh_name}"
    if opt:
        cell_id += "_opt-" + "-".join(sorted(opt))
    os.makedirs(out_dir, exist_ok=True)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "kind": shape.kind, "opt": sorted(opt)}

    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        result["skipped"] = reason
        _save(out_dir, cell_id, result)
        return result

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, donate = build_cell(arch, shape_name, mesh, opt=opt)
        with mesh:
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            coll = collective_bytes(compiled.as_text())
        result.update({
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes_est": (ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes),
            },
            "cost": {
                "flops": ca.get("flops", 0.0),
                "bytes_accessed": ca.get("bytes accessed", 0.0),
            },
            "collectives": coll,
        })
    except Exception as e:  # a failing cell is a bug; record it loudly
        result.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
    _save(out_dir, cell_id, result)
    return result


def _save(out_dir, cell_id, result):
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(result, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="one arch id (default: all, incl. the paper's)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opt", default="",
                    help="comma list: kv8,dp,compress,rematdots,int8fwd (§Perf)")
    args = ap.parse_args()
    opt = tuple(o for o in args.opt.split(",") if o)

    archs = [args.arch] if args.arch else ARCHS + [PAPER_ARCH]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                r = run_cell(arch, shape, mp, args.out, opt=opt)
                tag = ("SKIP" if "skipped" in r
                       else "OK" if r.get("ok") else "FAIL")
                n_ok += tag == "OK"
                n_skip += tag == "SKIP"
                n_fail += tag == "FAIL"
                extra = ""
                if tag == "OK":
                    gb = r["memory"]["peak_bytes_est"] / 2**30
                    extra = (f" mem/dev={gb:.2f}GiB "
                             f"gflops={r['cost']['flops'] / 1e9:.1f} "
                             f"coll={r['collectives']['total'] / 2**20:.0f}MiB "
                             f"compile={r['compile_s']:.0f}s")
                elif tag == "FAIL":
                    extra = " " + r["error"][:160]
                print(f"[{tag}] {arch} {shape} "
                      f"{'2x16x16' if mp else '16x16'}{extra}", flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
