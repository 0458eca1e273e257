import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay the first statements in this file — jax locks
the device count at first init, and the dry-run needs 512 placeholder host
devices to build the production meshes (16x16 single-pod, 2x16x16
multi-pod). Nothing else in the repo sets this flag.

Per cell this script:
  1. builds the abstract train/prefill/decode step for the architecture,
  2. ``jax.jit(...).lower(**input_specs).compile()`` on the production mesh,
  3. records ``memory_analysis()`` (fits-per-device proof),
     ``cost_analysis()`` (XLA's own numbers, loop bodies counted once), and
     the loop-corrected HLO analysis (flops / bytes / collective bytes —
     see hlo_analysis.py) from which EXPERIMENTS.md §Roofline is derived.

Results are written incrementally to ``results/dryrun.json`` so interrupted
runs resume; ``--only-missing`` skips completed cells.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import build
from repro.optim.adamw import AdamWConfig
from repro.runtime import sharding as sh
from repro.runtime.train_loop import init_train_state, make_train_step

# v5e roofline constants (per assignment)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "results", "dryrun.json")


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ArchConfig, shape: ShapeSpec, kind: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        batch = {"tokens": sds((b, s + 1), jnp.int32)}
        seq = s
    elif kind == "prefill":
        batch = {"tokens": sds((b, s), jnp.int32)}
        seq = s
    else:  # decode: one new token against a seq_len-deep cache
        batch = {"tokens": sds((b, 1), jnp.int32)}
        seq = 1
    if cfg.family == "vlm":
        n_patch = min(64, max(1, seq // 2))
        batch["patch_embeds"] = sds((b, n_patch, cfg.d_model), jnp.float32)
        batch["mrope_positions"] = sds((b, 3, seq), jnp.int32)
    if cfg.family == "encdec":
        batch["frames"] = sds((b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


def model_flops(cfg: ArchConfig, shape: ShapeSpec, kind: str) -> float:
    """Useful MODEL_FLOPS: 6·N·D train (bwd+fwd), 2·N·D prefill, 2·N·B
    decode; N counts matmul-visible params (embedding gather excluded,
    unembed projection included)."""
    n = cfg.active_params() if cfg.family == "moe" else cfg.num_params()
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.d_model  # the lookup-only table
    if kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# Per-arch microbatching (gradient accumulation): the standard knob for the
# largest train cells; the global batch is unchanged.
GRAD_ACCUM = {"qwen2_vl_7b": 2, "moonlight_16b_a3b": 2}


def build_cell(arch_id: str, shape_name: str, mesh, remat: str = "full",
               compress_grads: bool = False,
               grad_accum: int | None = None,
               serve_dtype: str = "bfloat16",
               serve_fsdp: bool = False,
               fsdp_gather_step: bool = False,
               cast_params_once: bool = False):
    """Returns (jitted_fn, example_abstract_args) for one cell.

    ``serve_dtype``: weights dtype for prefill/decode cells — bf16 by
    default (serving loads checkpoints cast down; keeping f32 masters
    doubles weight residency and every FSDP gather)."""
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    kind = shape.kind
    if grad_accum is None:
        grad_accum = GRAD_ACCUM.get(arch_id, 1)
    bundle = build(cfg, remat=remat)
    batch_abs = input_specs(cfg, shape, kind)
    batch_sh = {k: sh.token_sharding(mesh, len(v.shape),
                                     batch_size=v.shape[0])
                for k, v in batch_abs.items()}


    if kind == "train":
        opt_cfg = AdamWConfig()
        state_abs = jax.eval_shape(
            lambda k: init_train_state(bundle, k, opt_cfg,
                                       compress_grads=compress_grads),
            jax.random.key(0))
        param_sh = sh.param_shardings(state_abs["params"], mesh)
        opt_sh = {k: (param_sh if k in ("m", "v", "ef")
                      else sh.replicated(mesh))
                  for k in state_abs["opt"]}
        state_sh = {"params": param_sh, "opt": opt_sh}
        gather_specs = None
        if fsdp_gather_step:
            from jax.sharding import PartitionSpec as P
            specs = sh.param_specs(state_abs["params"], mesh)
            gather_specs = jax.tree.map(
                lambda s: P(*[None if a == "data" else a for a in s]),
                specs, is_leaf=lambda s: isinstance(s, P))
        step = make_train_step(bundle, opt_cfg, compress_grads=compress_grads,
                               grad_accum=grad_accum,
                               cast_params_once=cast_params_once,
                               param_gather_specs=gather_specs)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, sh.replicated(mesh)),
                     donate_argnums=(0,))
        return fn, (state_abs, batch_abs)

    params_abs = jax.eval_shape(bundle.init, jax.random.key(0))
    if serve_dtype != "float32":
        params_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.dtype(serve_dtype)
                if a.dtype == jnp.float32 else a.dtype), params_abs)
    param_sh = sh.param_shardings(params_abs, mesh, fsdp=serve_fsdp)
    if kind == "prefill":
        max_len = shape.seq_len
        def prefill_step(params, batch):
            return bundle.prefill_fn(params, batch, max_len)
        cache_abs = jax.eval_shape(
            lambda p, b: bundle.prefill_fn(p, b, max_len)[1],
            params_abs, batch_abs)
        cache_sh = sh.cache_shardings(cache_abs, mesh)
        # logits (B, S, padded_vocab): batch over DP, vocab over model —
        # gathering the vocab dim on output would cost 30+ GiB/device on
        # the 256k-vocab archs
        logits_sh = sh.logits_sharding(mesh, 3, shape.global_batch,
                                       cfg.padded_vocab)
        fn = jax.jit(prefill_step, in_shardings=(param_sh, batch_sh),
                     out_shardings=(logits_sh, cache_sh))
        return fn, (params_abs, batch_abs)

    # decode / serve_step. Caches prefer kv-head sharding: the dynamic
    # per-position cache write (DUS) must stay shard-local, which a
    # sequence-sharded cache breaks (GSPMD gathers the whole cache).
    cache_abs = jax.eval_shape(
        lambda: bundle.init_cache(shape.global_batch, shape.seq_len))
    cache_sh = sh.cache_shardings(cache_abs, mesh, prefer="heads")

    def serve_step(params, cache, tokens, pos):
        return bundle.decode_fn(params, cache, tokens, pos)

    tok_abs = batch_abs["tokens"]
    pos_abs = sds((), jnp.int32)
    tok_sh = sh.token_sharding(mesh, 2, batch_size=shape.global_batch)
    logits_sh = sh.logits_sharding(mesh, 2, shape.global_batch,
                                   cfg.padded_vocab)
    fn = jax.jit(serve_step,
                 in_shardings=(param_sh, cache_sh, tok_sh,
                               sh.replicated(mesh)),
                 out_shardings=(logits_sh, cache_sh),
                 donate_argnums=(1,))
    return fn, (params_abs, cache_abs, tok_abs, pos_abs)


def roofline(analysis: dict, cfg: ArchConfig, shape: ShapeSpec,
             kind: str, n_chips: int) -> dict:
    t_compute = analysis["flops"] / PEAK_FLOPS
    t_memory = analysis["bytes"] / HBM_BW
    t_coll = analysis["collective_bytes"] / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, kind)
    useful_t = mf / (n_chips * PEAK_FLOPS)
    bound = max(terms.values())
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_global": mf,
        "hlo_flops_per_device": analysis["flops"],
        "useful_flops_ratio": (mf / n_chips) / max(analysis["flops"], 1.0),
        "roofline_fraction": useful_t / bound if bound > 0 else 0.0,
    }


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             remat: str = "full", compress_grads: bool = False) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch_id, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": shape.kind, "ok": False}
    from repro.models import layers as model_layers
    try:
        with mesh:
            dp_size = 1
            for a in sh.batch_axes(mesh):
                dp_size *= mesh.shape[a]
            model_layers.set_activation_sharding(
                sh.batch_axes(mesh), dp_size, "model", mesh.shape["model"])
            fn, args = build_cell(arch_id, shape_name, mesh, remat=remat,
                                  compress_grads=compress_grads)
            t0 = time.time()
            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_estimate_bytes": (ma.argument_size_in_bytes
                                        + ma.temp_size_in_bytes
                                        + ma.output_size_in_bytes
                                        - ma.alias_size_in_bytes),
            }
            ca = compiled.cost_analysis()
            rec["xla_cost_analysis"] = {
                "flops_loop_body_once": ca.get("flops", -1.0),
                "bytes_loop_body_once": ca.get("bytes accessed", -1.0),
            }
            t0 = time.time()
            summary = hlo_analysis.analyze(compiled.as_text())
            rec["analysis_s"] = round(time.time() - t0, 2)
            rec["analysis"] = summary.to_json()
            rec["roofline"] = roofline(rec["analysis"], cfg, shape,
                                       shape.kind, n_chips)
            rec["ok"] = True
    except Exception as e:  # record the failure, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        model_layers.clear_activation_sharding()
    return rec


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch in ARCH_IDS:
        for shape_name in cells(arch):
            out.append((arch, shape_name))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--only-missing", action="store_true")
    ap.add_argument("--out", default=RESULTS_PATH)
    args = ap.parse_args()

    out_path = os.path.abspath(args.out)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    results: dict[str, dict] = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)

    todo = all_cells()
    if args.arch:
        todo = [(a, s) for a, s in todo if a == args.arch]
    if args.shape:
        todo = [(a, s) for a, s in todo if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for arch, shape_name in todo:
        for multi in meshes:
            key = f"{arch}/{shape_name}/{'2x16x16' if multi else '16x16'}"
            if args.compress_grads:
                key += "/compressed"
            if args.only_missing and results.get(key, {}).get("ok"):
                continue
            print(f"[dryrun] {key} ...", flush=True)
            rec = run_cell(arch, shape_name, multi, remat=args.remat,
                           compress_grads=args.compress_grads)
            results[key] = rec
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)
            if rec["ok"]:
                r = rec["roofline"]
                print(f"  ok compile={rec['compile_s']}s "
                      f"peak_mem={rec['memory']['peak_estimate_bytes']/2**30:.2f}GiB "
                      f"dominant={r['dominant']} "
                      f"roofline_frac={r['roofline_fraction']:.3f}",
                      flush=True)
            else:
                print(f"  FAIL {rec['error']}", flush=True)

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"[dryrun] {n_ok}/{len(results)} cells ok -> {out_path}")


if __name__ == "__main__":
    main()
