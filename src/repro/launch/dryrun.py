import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --------------------------------------------------------------------------
# Multi-pod dry-run (deliverable e): lower + compile every
# (architecture x input shape x mesh) cell on 512 placeholder devices and
# record memory_analysis / cost_analysis / per-collective byte counts.
#
# The two lines above MUST precede every other import (jax locks the device
# count at first init).  Do not set the flag anywhere global — smoke tests and
# benches must see 1 device.
# --------------------------------------------------------------------------
import argparse       # noqa: E402
import json           # noqa: E402
import sys            # noqa: E402
import time           # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp                    # noqa: E402
import numpy as np    # noqa: E402

from repro.configs import base as cb       # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch import hlo_analysis      # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import lm, params as pm  # noqa: E402
from repro.train import loop as train_loop  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "results", "dryrun")

# v5e roofline constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link


def input_specs(cfg, shape: cb.ShapeConfig, rules):
    """ShapeDtypeStruct stand-ins for every model input of one cell."""
    B, S = shape.global_batch, shape.seq_len
    tok_shard = rules.sharding(("batch", None))
    if shape.kind == "train":
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_shard),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_shard),
        }
        if cfg.is_encdec:
            # src/tgt split S/2 each (DESIGN.md §4)
            batch["tokens"] = jax.ShapeDtypeStruct((B, S // 2), jnp.int32, sharding=tok_shard)
            batch["labels"] = jax.ShapeDtypeStruct((B, S // 2), jnp.int32, sharding=tok_shard)
            batch["src_frames"] = jax.ShapeDtypeStruct(
                (B, S // 2, cfg.d_model), jnp.bfloat16,
                sharding=rules.sharding(("batch", None, None)))
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_shard)}
        if cfg.is_encdec:
            batch["tokens"] = jax.ShapeDtypeStruct((B, S // 2), jnp.int32, sharding=tok_shard)
            batch["src_frames"] = jax.ShapeDtypeStruct(
                (B, S // 2, cfg.d_model), jnp.bfloat16,
                sharding=rules.sharding(("batch", None, None)))
        return batch
    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_shard)}
    raise ValueError(shape.kind)


def cache_specs(cfg, shape: cb.ShapeConfig, rules):
    """ShapeDtypeStructs for the decode-step KV/state caches."""
    B, S = shape.global_batch, shape.seq_len
    src_len = S // 2 if cfg.is_encdec else None
    s_cache = S // 2 if cfg.is_encdec else S
    caches = jax.eval_shape(lambda: lm.init_caches(cfg, B, s_cache, src_len=src_len))
    axes = lm.cache_axes(cfg)

    def attach(sds, ax):
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=rules.sharding(ax))

    return jax.tree.map(attach, caches, axes)


def make_rules_for(cfg, mesh, shape: cb.ShapeConfig | None = None):
    return shd.make_rules(
        mesh,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, n_experts=cfg.n_experts,
        d_ff=cfg.d_ff, d_model=cfg.d_model, vocab_size=cfg.vocab_size,
        fsdp=cfg.fsdp, expert_fsdp=cfg.expert_fsdp,
        global_batch=shape.global_batch if shape else 0,
        pure_dp=(cfg.sharding_profile == "pure_dp"),
    )


#: fields neutralized under --profile=baseline (the paper-faithful, uniform
#: naive-TP reference the §Perf hillclimb measures against)
_BASELINE_OVERRIDES = dict(
    sharding_profile="tp", microbatches=1, remat_policy="full",
    capacity_factor=1.25, zero1=True, grad_dtype="float32",
    mlstm_chunk=64, quad_dtype="float32", moe_impl="gather_weights",
    mamba_split_proj=False,
)


def lower_cell(cfg, shape: cb.ShapeConfig, mesh):
    """Build the jitted step for one cell.

    Returns (lowered, jaxpr_stats) — jaxpr_stats carries scan-trip-exact
    logical FLOPs + dot-traffic bytes (hlo_analysis), since XLA's
    cost_analysis counts while bodies once.
    """
    rules = make_rules_for(cfg, mesh, shape)
    if shape.kind == "train":
        tcfg = train_loop.TrainConfig()
        step, state_sh, (pspecs, m_specs, v_specs) = train_loop.jit_train_step(cfg, tcfg, rules)
        state_structs = train_loop.TrainState(
            params=pm.shape_structs(pspecs, rules),
            opt=train_loop.AdamState(
                m=pm.shape_structs(m_specs, rules),
                v=pm.shape_structs(v_specs, rules),
                step=jax.ShapeDtypeStruct((), jnp.int32),
            ),
        )
        args = (state_structs, input_specs(cfg, shape, rules))
        raw_fn = train_loop.make_train_step(cfg, tcfg, rules)
        stats = hlo_analysis.trace_stats(raw_fn, *args)
        return step.lower(*args), stats
    pspecs = lm.model_specs(cfg)
    param_structs = pm.shape_structs(pspecs, rules)
    if shape.kind == "prefill":
        def fn(params, batch):
            with shd.use_rules(rules):
                return lm.prefill(params, cfg, batch)
        args = (param_structs, input_specs(cfg, shape, rules))
        stats = hlo_analysis.trace_stats(fn, *args)
        return jax.jit(fn).lower(*args), stats
    # decode
    def fn(params, tokens, caches):
        with shd.use_rules(rules):
            return lm.decode_step(params, cfg, tokens, caches)
    args = (param_structs, input_specs(cfg, shape, rules)["tokens"],
            cache_specs(cfg, shape, rules))
    stats = hlo_analysis.trace_stats(fn, *args)
    return jax.jit(fn, donate_argnums=(2,)).lower(*args), stats


def lower_esam(mesh, optimized: bool = False):
    """The paper's own system as a dry-run cell: batched binary-SNN inference,
    data-parallel over the full mesh.

    optimized=False: the int32 functional plane (decode to {-1,+1} int32,
    int32 einsum, int32 V_mem written per tile) — a direct transcription of
    the hardware semantics.
    optimized=True (§Perf/HC3): int8 spike/weight operands with int32 MXU
    accumulation and the threshold compare fused into each tile so V_mem never
    round-trips — 4x less operand traffic, int8 outputs between tiles.
    """
    from repro.configs import esam_mnist as em
    from repro.core.esam import tile as esam_tile

    # HC3 iter2: baseline rules park the batch on the data axis only, idling
    # 15/16 of the mesh; optimized spreads it over every axis (weights are
    # 41 KB of bits — replication is free).  The roofline *terms* are
    # formula-identical (they already divide by all chips), but realized time
    # changes 16x: §Perf records utilization alongside the terms.
    rules = shd.make_rules(mesh, n_heads=1, n_kv_heads=1, vocab_size=0,
                           pure_dp=optimized)
    topo = em.TOPOLOGY

    def serve_step(weights, vth, spikes):
        with shd.use_rules(rules):
            s = spikes
            if optimized:
                s = s.astype(jnp.int8)
                for i, (w, t) in enumerate(zip(weights, vth)):
                    s = shd.constrain(s, "batch", None)
                    w_signed = (2 * w - 1).astype(jnp.int8)
                    vmem = jax.lax.dot_general(
                        s, w_signed, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    if i < len(weights) - 1:
                        s = (vmem >= t).astype(jnp.int8)   # fused fire
                return jnp.argmax(vmem, axis=-1)
            for i, (w, t) in enumerate(zip(weights, vth)):
                s = shd.constrain(s, "batch", None)
                s, vmem = esam_tile.functional_tile(w, s, t)
            return jnp.argmax(vmem, axis=-1)

    w_structs = [
        jax.ShapeDtypeStruct((topo[i], topo[i + 1]), jnp.int8,
                             sharding=rules.sharding((None, None)))
        for i in range(len(topo) - 1)
    ]
    vth_structs = [
        jax.ShapeDtypeStruct((topo[i + 1],), jnp.int32, sharding=rules.sharding((None,)))
        for i in range(len(topo) - 1)
    ]
    spikes = jax.ShapeDtypeStruct((em.ESAM_BATCH, topo[0]), jnp.bool_,
                                  sharding=rules.sharding(("batch", None)))
    args = (w_structs, vth_structs, spikes)
    stats = hlo_analysis.trace_stats(serve_step, *args)
    return jax.jit(serve_step).lower(*args), stats


def model_flops(cfg, shape: cb.ShapeConfig) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) reference FLOPs for the cell."""
    specs = lm.model_specs(cfg)
    n_params = pm.param_count(specs)
    if cfg.n_experts:
        # active = non-expert params + top_k/E of expert params
        expert = sum(
            int(np.prod(s.shape)) for k, s in _named_leaves(specs)
            if "w_gate" in k or "w_up" in k or "w_down" in k
        )
        n_active = (n_params - expert) + expert * cfg.top_k / cfg.n_experts
    else:
        n_active = n_params
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else
                                   (shape.seq_len if shape.kind == "prefill" else 1))
    if cfg.is_encdec and shape.kind in ("train", "prefill"):
        tokens = shape.global_batch * shape.seq_len // 2
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def _named_leaves(tree, prefix=""):
    from repro.models.params import is_spec
    out = []
    if is_spec(tree):
        return [(prefix, tree)]
    if isinstance(tree, dict):
        for k, v in tree.items():
            out += _named_leaves(v, f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out += _named_leaves(v, f"{prefix}/{i}")
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             profile: str = "baseline") -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_chips = 512 if multi_pod else 256
    key = f"{arch}__{shape_name}__{mesh_name}"
    if arch == "esam-mnist":
        (lowered, stats) = lower_esam(mesh, optimized=(profile == "optimized"))
        mflops = 2.0 * 330_000 * 65536  # 2*synapses*batch
        cfg = None
    else:
        import dataclasses as _dc
        cfg = cb.get(arch)
        if profile == "baseline":
            cfg = _dc.replace(cfg, **_BASELINE_OVERRIDES)
        shape = cb.SHAPES[shape_name]
        lowered, stats = lower_cell(cfg, shape, mesh)
        mflops = model_flops(cfg, shape)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = dict(compiled.cost_analysis())
    coll = hlo_analysis.collective_bytes(compiled.as_text())

    # logical (jaxpr, scan-exact) workload — primary roofline source;
    # raw XLA cost_analysis kept for cross-checking (undercounts loop bodies)
    flops = float(stats["flops"])
    bytes_traffic = float(stats["dot_bytes"])
    coll_total = sum(coll.values()) * n_chips      # per-device HLO -> fleet-wide
    result = {
        "key": key,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "chips": n_chips,
        "flops": flops,
        "bytes_traffic": bytes_traffic,
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_bytes_total": coll_total,
        "model_flops": mflops,
        "memory": {
            "bytes_per_device_argument": getattr(mem, "argument_size_in_bytes", None),
            "bytes_per_device_output": getattr(mem, "output_size_in_bytes", None),
            "bytes_per_device_temp": getattr(mem, "temp_size_in_bytes", None),
            "bytes_per_device_peak": getattr(mem, "peak_memory_in_bytes", None),
            "generated_code": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "roofline": {
            "compute_s": flops / (n_chips * PEAK_FLOPS),
            "memory_s": bytes_traffic / (n_chips * HBM_BW),
            "collective_s": coll_total / (n_chips * ICI_BW),
        },
        "wall_s": time.time() - t0,
    }
    r = result["roofline"]
    result["bottleneck"] = max(r, key=r.get)
    result["useful_flops_frac"] = mflops / flops if flops else None
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, key + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] {key}: flops={flops:.3e} bytes={bytes_traffic:.3e} "
          f"coll={coll_total:.3e} bottleneck={result['bottleneck']} "
          f"({result['wall_s']:.0f}s)")
    print(f"[dryrun]   memory_analysis: {mem}")
    return result


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in cb.ARCH_IDS:
        cfg = cb.get(arch)
        for shape_name in cb.applicable_shapes(cfg):
            cells.append((arch, shape_name))
    cells.append(("esam-mnist", "batch64k"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all applicable)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default=os.path.normpath(RESULTS_DIR))
    ap.add_argument("--profile", choices=["baseline", "optimized"], default="baseline",
                    help="baseline: uniform naive-TP reference; optimized: "
                         "per-arch tuned knobs (EXPERIMENTS §Perf)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    if args.profile == "optimized" and args.out == os.path.normpath(RESULTS_DIR):
        args.out = args.out.replace("dryrun", "perf")

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch or cb.ALIASES.get(a) == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape_name in cells:
        for multi_pod in meshes:
            mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
            key = f"{arch}__{shape_name}__{mesh_name}"
            path = os.path.join(args.out, key + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip {key} (cached)")
                continue
            try:
                run_cell(arch, shape_name, multi_pod, args.out, profile=args.profile)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((key, f"{type(e).__name__}: {e}"))
                print(f"[dryrun] FAIL {key}: {type(e).__name__}: {str(e)[:500]}")
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for k, msg in failures:
            print(f"  {k}: {msg[:300]}")
        sys.exit(1)
    print("\n[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
