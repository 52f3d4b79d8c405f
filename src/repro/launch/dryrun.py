import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the first two lines, before ANY other import: jax locks the
# device count at first init, and the dry-run needs 512 placeholder host
# devices to build the production meshes.  (Tests/benches import other
# modules and correctly see 1 device.)

import argparse            # noqa: E402
import dataclasses         # noqa: E402
import json                # noqa: E402
import time                # noqa: E402
import traceback           # noqa: E402
from typing import Optional  # noqa: E402

import jax                 # noqa: E402
import jax.numpy as jnp    # noqa: E402
import numpy as np         # noqa: E402

from repro.analysis.hlo import analyze_hlo                     # noqa: E402
from repro.configs import ARCH_IDS, get_config                 # noqa: E402
from repro.configs.shapes import SHAPES, shapes_for, skip_reason  # noqa: E402
from repro.distributed.logical import logical_rules                 # noqa: E402
from repro.distributed.sharding import (cache_shardings,       # noqa: E402
                                        param_shardings,
                                        token_sharding)
from repro.launch.mesh import (HBM_PER_CHIP, HBM_BW, ICI_BW_PER_LINK,  # noqa: E402
                               PEAK_FLOPS_BF16, make_production_mesh)
from repro.launch.specs import input_specs, params_specs       # noqa: E402
from repro.models.registry import model_for                    # noqa: E402
from repro.optim import adamw                                  # noqa: E402
from repro.optim.adamw import AdamWConfig                      # noqa: E402
from repro.training.trainer import TrainConfig, make_train_step  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# Per-arch training knobs (activation-memory napkin math in EXPERIMENTS.md
# §Dry-run): microbatch counts keep layer-boundary residuals under HBM.
MICROBATCHES = {
    "chameleon_34b": 16, "codeqwen15_7b": 4, "qwen3_14b": 2,
    "starcoder2_3b": 2, "h2o_danube_1_8b": 4, "mixtral_8x7b": 4,
    "deepseek_v3_671b": 16, "zamba2_7b": 4, "xlstm_125m": 1,
    "whisper_medium": 8,
}
# FSDP/ZeRO-3 param+moment sharding for the larger archs
ZERO3 = {"chameleon_34b", "codeqwen15_7b", "qwen3_14b", "mixtral_8x7b",
         "deepseek_v3_671b", "zamba2_7b"}
# bf16 moments for the biggest (MaxText convention)
BF16_MOMENTS = {"deepseek_v3_671b", "chameleon_34b"}


def _train_config(arch: str) -> TrainConfig:
    return TrainConfig(
        microbatches=MICROBATCHES.get(arch, 1),
        remat=True,
        optimizer=AdamWConfig(
            moment_dtype="bfloat16" if arch in BF16_MOMENTS else "float32"))


def logical_rules_for(cfg, mesh) -> dict:
    """Bind logical activation axes to mesh axes per arch (DESIGN.md §3).

    heads→'model' when the head count divides TP; otherwise the query
    sequence is context-parallel over 'model' (starcoder2's 24 heads,
    qwen3's 40 heads).  KV stays replicated in that case (cheap: GQA).
    """
    d = [a for a in ("pod", "data") if a in mesh.shape]
    batch_ax = tuple(d) if len(d) > 1 else (d[0] if d else None)
    m = mesh.shape["model"]
    rules = {"batch": batch_ax, "ff": "model", "moe_ff": "model"}
    data = mesh.shape.get("data", 1)
    if cfg.n_experts and cfg.n_experts % (m * data) == 0:
        rules["experts"] = ("model", "data")   # matches 2-D EP weights
    elif cfg.n_experts and cfg.n_experts % m == 0:
        rules["experts"] = "model"
    if cfg.n_heads % m == 0:
        rules["heads"] = "model"
        if cfg.n_kv_heads % m == 0:
            rules["kv_heads"] = "model"
    else:
        rules["q_seq"] = "model"
    return rules


def build_lowerable(arch: str, shape_name: str, mesh):
    """Returns (fn, arg_specs, in_shardings, donate_argnums)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = model_for(cfg)
    bundle = input_specs(cfg, shape)
    p_specs = params_specs(cfg)
    p_sh = param_shardings(p_specs, mesh, zero3=arch in ZERO3)
    dsize = int(np.prod([mesh.shape[a] for a in ("pod", "data")
                         if a in mesh.shape]))
    batch_shardable = shape.global_batch % dsize == 0
    tok_sh = token_sharding(mesh, shardable_batch=batch_shardable)

    if shape.kind == "train":
        tcfg = _train_config(arch)
        # per-microbatch batch must still divide the data axes
        m = tcfg.microbatches
        while m > 1 and (shape.global_batch // m) % max(1, dsize) != 0:
            m //= 2
        if m != tcfg.microbatches:
            tcfg = dataclasses.replace(tcfg, microbatches=m)
        step = make_train_step(cfg, tcfg)
        opt_specs = jax.eval_shape(
            lambda: adamw.init(tcfg.optimizer, p_specs))
        opt_sh = adamw.AdamWState(
            step=jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec()),
            mu=jax.tree_util.tree_map(lambda s, sh: sh, opt_specs.mu, p_sh),
            nu=jax.tree_util.tree_map(lambda s, sh: sh, opt_specs.nu, p_sh))
        args = (p_specs, opt_specs) + bundle.args
        in_sh = (p_sh, opt_sh) + (tok_sh,) * 2
        if cfg.is_encdec:
            in_sh = in_sh + (jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(
                    tok_sh.spec[0], None, None)),)
        fn = step
        out_sh = (p_sh, opt_sh, None)
        return fn, args, in_sh, out_sh, (0, 1)

    if shape.kind == "prefill":
        def fn(params, tokens, *extra):
            kw = {}
            if cfg.is_encdec:
                kw["frame_embeddings"] = extra[0]
            logits, _ = model.forward(params, cfg, tokens, **kw)
            return logits
        args = (p_specs,) + bundle.args
        in_sh = (p_sh, tok_sh)
        if cfg.is_encdec:
            in_sh = in_sh + (jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(tok_sh.spec[0], None,
                                                 None)),)
        return fn, args, in_sh, None, ()

    # decode
    def fn(params, cache, tokens):
        return model.decode_step(params, cfg, cache, tokens)
    c_sh = cache_shardings(bundle.cache, mesh, shape.global_batch)
    args = (p_specs, bundle.cache) + bundle.args
    in_sh = (p_sh, c_sh, tok_sh)
    out_sh = (None, c_sh)
    return fn, args, in_sh, out_sh, (1,)


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    out = {}
    if ma is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "kind": shape.kind}
    if reason:
        rec["status"] = "skip"
        rec["skip_reason"] = reason
        _save(rec, save)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    # lint: allow(det-wallclock): host compile timing, never sim state
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh, donate = build_lowerable(arch, shape_name,
                                                          mesh)
        rules = logical_rules_for(cfg, mesh)
        rec["logical_rules"] = {k: str(v) for k, v in rules.items()}
        with mesh, logical_rules(mesh, rules):
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            # lint: allow(det-wallclock): host compile timing
            t_lower = time.time() - t0
            compiled = lowered.compile()
            # lint: allow(det-wallclock): host compile timing
            t_compile = time.time() - t0 - t_lower
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        ana = analyze_hlo(hlo)
        mem = _memory_dict(compiled)

        per_dev_bytes = sum(mem.get(k, 0) for k in
                            ("argument_size_in_bytes", "temp_size_in_bytes",
                             "output_size_in_bytes")) \
            - mem.get("alias_size_in_bytes", 0)
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        n_active = cfg.active_param_count()
        model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens

        flops_dev = ana.dot_flops
        compute_term = flops_dev / PEAK_FLOPS_BF16
        memory_term = ana.hbm_bytes / HBM_BW
        collective_term = ana.collective_bytes / ICI_BW_PER_LINK
        terms = {"compute_s": compute_term, "memory_s": memory_term,
                 "collective_s": collective_term}
        dominant = max(terms, key=terms.get)

        rec.update({
            "status": "ok",
            "n_devices": n_dev,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "cost_analysis_flops": float(ca.get("flops", -1.0)),
            "cost_analysis_bytes": float(ca.get("bytes accessed", -1.0)),
            "hlo_dot_flops_per_dev": flops_dev,
            "hlo_hbm_bytes_per_dev": ana.hbm_bytes,
            "hlo_collective_bytes_per_dev": ana.collective_bytes,
            "collective_breakdown": ana.collective_breakdown,
            "memory_analysis": mem,
            "per_device_bytes": int(per_dev_bytes),
            "fits_hbm": bool(per_dev_bytes <= HBM_PER_CHIP),
            "model_flops_total": float(model_flops),
            "useful_flops_ratio": float(model_flops
                                        / max(1.0, flops_dev * n_dev)),
            "roofline_terms_s": terms,
            "dominant_term": dominant,
        })
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool) -> None:
    if not save:
        return
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else [s.name for s in shapes_for(cfg)]
                  + [s for s in SHAPES
                     if skip_reason(cfg, SHAPES[s])])
        for shape_name in shapes:
            for multi in meshes:
                rec = run_cell(arch, shape_name, multi)
                status = rec["status"]
                mesh_name = rec["mesh"]
                if status == "ok":
                    mem = rec["per_device_bytes"] / 2**30
                    print(f"[OK]   {arch:18s} {shape_name:12s} {mesh_name:10s}"
                          f" compile={rec['compile_s']:.1f}s"
                          f" mem/dev={mem:.2f}GiB fits={rec['fits_hbm']}"
                          f" dom={rec['dominant_term']}")
                elif status == "skip":
                    print(f"[SKIP] {arch:18s} {shape_name:12s} {mesh_name:10s}"
                          f" ({rec['skip_reason'][:60]})")
                else:
                    failures += 1
                    print(f"[FAIL] {arch:18s} {shape_name:12s} {mesh_name:10s}"
                          f" {rec['error'][:140]}")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
