"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape) on the production meshes, print
memory_analysis / cost_analysis, and extract roofline terms.

The platform and device-count env vars are set below BEFORE any jax
import — jax locks both on first init. The analysis runs on 512 virtual
CPU devices, pinned to the CPU so that it never takes a chip this machine
may have. Do not import this module from processes that need a 1-device
view (tests, benches); run it as __main__:

    python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, memory_report, model_flops_for
from repro.models.model import Model
from repro.models.sharding import (RULE_PROFILES, ShardingRules,
                                   activation_sharding, logical_to_sharding)
from repro.rl.train_step import make_train_step
from repro.train.optimizer import AdamWConfig, adamw_init

RESULTS_DIR = "results/dryrun"


def _tree_shapes(tree):
    return jax.tree.map(lambda x: x.shape, tree)


def batch_sharding(mesh, rules, specs_dict):
    return {k: NamedSharding(mesh, rules.resolve(log, sds.shape, mesh))
            for k, (log, sds) in specs_dict.items()}


def lower_case(arch: str, shape_name: str, *, multi_pod: bool,
               rules: ShardingRules | None = None, microbatches: int = 8,
               remat: bool = True, rules_profile: str = "baseline"):
    """Returns (lowered, meta) for one (arch x shape x mesh) case."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": why}
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    if rules is None:
        rules = ShardingRules().with_overrides(
            **RULE_PROFILES.get(rules_profile, {}))

    params_abs = model.init_abstract()
    logical = model.logical_specs()
    param_sh = logical_to_sharding(logical, _tree_shapes(params_abs), mesh, rules)
    inputs = model.input_specs(shape)

    def in_sh(name, log):
        return NamedSharding(mesh, rules.resolve(log, inputs[name].shape, mesh))

    t0 = time.perf_counter()
    with mesh, activation_sharding(mesh, rules):
        if shape.kind == "train":
            opt_abs = jax.eval_shape(lambda p: adamw_init(p), params_abs)
            opt_sh = {"mu": param_sh, "nu": param_sh,
                      "step": NamedSharding(mesh, P())}
            state_abs = {"params": params_abs, "opt": opt_abs}
            state_sh = {"params": param_sh, "opt": opt_sh}
            mb = microbatches if shape.global_batch % microbatches == 0 else 1
            step_fn = make_train_step(model, AdamWConfig(),
                                      microbatches=mb, remat=remat)
            bsh = {"tokens": in_sh("tokens", ("batch", "seq")),
                   "labels": in_sh("labels", ("batch", "seq")),
                   "loss_mask": in_sh("loss_mask", ("batch", "seq")),
                   "advantages": in_sh("advantages", ("batch", "seq"))}
            if "frontend" in inputs:
                bsh["frontend"] = in_sh("frontend",
                                        ("batch", "frontend", None))
            lowered = jax.jit(
                step_fn, in_shardings=(state_sh, bsh),
                out_shardings=(state_sh, None)).lower(state_abs, inputs)
        elif shape.kind == "prefill":
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = logical_to_sharding(model.cache_logical_specs(),
                                           _tree_shapes(cache_abs), mesh, rules)

            def prefill(params, tokens, cache, frontend=None):
                return model.prefill(params, tokens, cache, frontend=frontend)

            ish = [param_sh, in_sh("tokens", ("batch", "seq")), cache_sh]
            args = [params_abs, inputs["tokens"], cache_abs]
            if "frontend" in inputs:
                ish.append(in_sh("frontend", ("batch", "frontend", None)))
                args.append(inputs["frontend"])
            lowered = jax.jit(prefill, in_shardings=tuple(ish),
                              out_shardings=(None, cache_sh)).lower(*args)
        else:  # decode
            ring = shape.name == "long_500k" and bool(cfg.sliding_window)

            def serve_step(params, token, cache):
                return model.decode_step(params, token, cache, ring=ring)

            cache_abs = inputs["cache"]
            cache_sh = logical_to_sharding(model.cache_logical_specs(),
                                           _tree_shapes(cache_abs), mesh, rules)
            # donate the KV cache (standard for serving): lets XLA update it
            # in place instead of materializing a full modified copy per step
            donate = (2,) if os.environ.get("DRYRUN_DONATE", "0") != "0" else ()
            lowered = jax.jit(
                serve_step,
                in_shardings=(param_sh, in_sh("token", ("batch", None)),
                              cache_sh),
                out_shardings=(None, cache_sh),
                donate_argnums=donate).lower(
                    params_abs, inputs["token"], cache_abs)
    meta = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "n_chips": mesh.size, "lower_s": time.perf_counter() - t0,
            "params_b": cfg.param_count() / 1e9,
            "active_params_b": cfg.active_param_count() / 1e9}
    return lowered, meta


def run_case(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = RESULTS_DIR, **kw) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
    path = os.path.join(out_dir, tag + ".json")
    try:
        lowered, meta = lower_case(arch, shape_name, multi_pod=multi_pod, **kw)
        if lowered is None:
            rec = {"arch": arch, "shape": shape_name,
                   "mesh": "multi" if multi_pod else "single",
                   "status": "skipped", "why": meta["skipped"]}
        else:
            t0 = time.perf_counter()
            compiled = lowered.compile()
            meta["compile_s"] = time.perf_counter() - t0
            mem = memory_report(compiled)
            cfg = get_config(arch)
            shape = SHAPES[shape_name]
            terms = analyze(compiled, n_chips=meta["n_chips"],
                            model_flops=model_flops_for(cfg, shape))
            if os.environ.get("DRYRUN_SAVE_HLO"):
                with open(os.path.join(out_dir, tag + ".hlo"), "w") as f:
                    f.write(compiled.as_text())
            print(f"[{tag}] memory_analysis: {mem}")
            print(f"[{tag}] cost_analysis: flops={terms.hlo_flops:.3e} "
                  f"bytes={terms.hlo_bytes:.3e}")
            rec = {"status": "ok", **meta, "memory": mem,
                   "roofline": terms.to_dict()}
    except Exception as e:  # record failures for triage, then re-raise intent
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[{tag}] -> {rec['status']}")
    return rec


def _main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        # orchestrate via subprocesses: one compile per process (resumable)
        cases = [(a, s, m) for a in list_archs() for s in SHAPES for m in meshes]
        for a, s, m in cases:
            tag = f"{a}_{s}_{'multi' if m else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                ok = json.load(open(path)).get("status")
                print(f"[{tag}] cached ({ok})")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s,
                   "--mesh", "multi" if m else "single", "--out", args.out,
                   "--microbatches", str(args.microbatches)]
            print("::", " ".join(cmd), flush=True)
            try:
                # the child inherits JAX_PLATFORMS=cpu from above and sets
                # it again on import, so it never takes a chip
                subprocess.run(cmd, timeout=3300)
            except subprocess.TimeoutExpired:
                with open(path, "w") as f:
                    json.dump({"arch": a, "shape": s,
                               "mesh": "multi" if m else "single",
                               "status": "timeout"}, f)
        return
    run_case(args.arch, args.shape, multi_pod=(meshes[0]),
             out_dir=args.out, microbatches=args.microbatches)


if __name__ == "__main__":
    _main()
