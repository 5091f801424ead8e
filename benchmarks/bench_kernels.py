"""Pallas kernel timings in BOTH lanes — interpret mode (the correctness
plane that runs everywhere) and the compiled path (TPU; skipped gracefully
elsewhere with a ``lane=compiled_skipped`` row) — so recorded speedups can
never be interpret-mode artifacts: every BENCH_kernels.json row carries its
lane name.

Headline section: the popcount-domain MAC + single-launch mega-kernel
cascade (``kernels/cim_popcount``) vs the unpack-then-MXU packed plane
(``cim_matmul_packed``) at the serving shape 1024x768x768.  The comparison
is *gated*: bit identity against the packed oracle is asserted before any
timing is recorded, and the popcount lanes must clear a >=1x floor over the
packed lanes in the same lane (SPEEDUP_FLOOR, recorded in the row).  Roofline
inputs per datapath come from ``cost_model.mac_datapath_stats`` so the
trajectory carries its own model next to the measurements.

Results go to ``BENCH_kernels.json`` (override with env BENCH_OUT).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import Recorder, time_call
except ModuleNotFoundError:  # direct `python benchmarks/bench_kernels.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))
    from benchmarks.common import Recorder, time_call
from repro.core import packing
from repro.core.esam import cost_model
from repro.kernels.arbiter import ops as arb_ops
from repro.kernels.cim_matmul import ops as cim_ops
from repro.kernels.cim_matmul_packed import ops as pk_ops
from repro.kernels.cim_popcount import ops as pop_ops
from repro.kernels.if_neuron import ops as if_ops
from repro.kernels.stdp import ops as stdp_ops

#: popcount lanes must be at least this much faster than the packed-MXU
#: lanes in the same lane (interpret vs interpret, compiled vs compiled)
SPEEDUP_FLOOR = 1.0


def _lanes(rec: Recorder, name: str, make_fn, derived: str, repeats: int = 1):
    """Record one kernel in both lanes; returns (us_interpret, us_compiled).

    ``make_fn(interpret)`` builds the timed call.  The compiled lane is
    attempted everywhere and skipped gracefully (recorded, not timed) where
    non-interpret Pallas does not lower — off-TPU backends.
    """
    us_i, _ = time_call(lambda: make_fn(True), repeats=repeats)
    rec.emit(f"{name}_interpret", us_i, f"lane=interpret;{derived}")
    try:
        us_c, _ = time_call(lambda: make_fn(False), repeats=repeats)
        rec.emit(f"{name}_compiled", us_c, f"lane=compiled;{derived}")
        return us_i, us_c
    except Exception as e:  # noqa: BLE001
        if jax.default_backend() == "tpu":
            raise
        rec.emit(
            f"{name}_compiled", 0.0,
            f"lane=compiled_skipped;backend={jax.default_backend()};"
            f"reason={type(e).__name__};{derived}")
        return us_i, None


def _roofline(datapath: str, B: int, K: int, N: int) -> str:
    r = cost_model.mac_datapath_stats(B, K, N, datapath)
    return (f"hbm_bytes={r['hbm_bytes']};compute_ops={r['compute_ops']};"
            f"unit={r['unit']};t_roofline_us={r['t_roofline_us']:.1f};"
            f"bound={r['bound']}")


def _popcount_comparison(rec: Recorder, key):
    """Popcount-domain MAC + mega cascade vs the packed-MXU plane, gated."""
    B, K, N = 1024, 768, 768
    s = jax.random.bernoulli(key, 0.4, (B, K)).astype(jnp.float32)
    w = jax.random.bernoulli(
        jax.random.fold_in(key, 1), 0.5, (K, N)).astype(jnp.int8)
    vth = jnp.zeros((N,), jnp.int32)
    packed = jax.block_until_ready(packing.pack_spikes(s))
    planes = jax.block_until_ready(packing.pack_weight_planes(w))

    # ---- bit-identity gate before anything is timed -------------------- #
    want = np.asarray(pk_ops.cim_matmul_packed(packed, w, interpret=True))
    got_ref = np.asarray(pop_ops.cim_popcount_ref(packed, planes))
    got_k = np.asarray(pop_ops.cim_popcount_matmul(
        packed, planes, use_kernel=True, interpret=True))
    assert np.array_equal(want, got_ref), "popcount ref != packed oracle"
    assert np.array_equal(want, got_k), "popcount kernel != packed oracle"

    bytes_packed = B * packing.packed_nbytes(K)
    us_pk_i, us_pk_c = _lanes(
        rec, f"kernel_cim_matmul_packed_{B}x{K}x{N}",
        lambda interp: pk_ops.cim_matmul_packed(packed, w, interpret=interp),
        f"spike_bytes_moved={bytes_packed};wire=uint32_bitplane;"
        f"unpack=vmem_shift_mask;{_roofline('packed_mxu', B, K, N)}")
    us_pc_i, us_pc_c = _lanes(
        rec, f"kernel_cim_popcount_{B}x{K}x{N}",
        lambda interp: pop_ops.cim_popcount_matmul(
            packed, planes, use_kernel=True, interpret=interp),
        f"spike_bytes_moved={bytes_packed};wire=uint32_bitplane;"
        f"mac=and_popcount;unpack=none;{_roofline('popcount_vpu', B, K, N)}")
    us_ref, _ = time_call(
        lambda: pop_ops.cim_popcount_matmul(packed, planes, use_kernel=False),
        repeats=1)
    rec.emit(
        f"kernel_cim_popcount_ref_{B}x{K}x{N}", us_ref,
        "lane=jnp_ref;dispatch=non_tpu_backends;mac=and_popcount")

    _lanes(
        rec, f"kernel_esam_layer_popcount_fused_{B}x{K}x{N}",
        lambda interp: pop_ops.esam_layer_popcount(
            packed, planes, vth, use_kernel=True, interpret=interp),
        f"fused=popcount_mac+if_fire+repack;out_bytes={B * N // 8};"
        f"inter_tile_wire=uint32_bitplane")

    # ---- whole cascade: per-tile packed launches vs ONE mega launch ---- #
    from repro.core.esam import plan as plan_mod

    topo = (K, N, N, 10)
    wb = [jax.random.bernoulli(
        jax.random.fold_in(key, 10 + i), 0.5,
        (topo[i], topo[i + 1])).astype(jnp.int8) for i in range(3)]
    vths = [jnp.full((topo[i + 1],), 96, jnp.int32) for i in range(3)]
    tile_planes = [packing.pack_weight_planes(x) for x in wb]
    w_stack, vth_stack = pop_ops.stack_cascade_operands(tile_planes, vths, topo)
    w_stack = jax.block_until_ready(w_stack)

    def packed_cascade(interp):
        p = plan_mod._packed_cascade(wb, vths, packed, interpret=interp)
        return pk_ops.cim_matmul_packed(p, wb[-1], interpret=interp)

    def mega_cascade(interp):
        return pop_ops.esam_cascade_popcount(
            packed, w_stack, vth_stack, topology=topo,
            use_kernel=True, interpret=interp)

    want_l = packed_cascade(True)
    got_l, _ = mega_cascade(True)
    assert np.array_equal(np.asarray(want_l), np.asarray(got_l)), \
        "mega cascade logits != per-tile packed cascade"
    n_launches = len(topo) - 1  # fused hidden tiles + readout vs 1 mega launch
    us_cc_i, us_cc_c = _lanes(
        rec, f"cascade_packed_per_tile_{B}x{'x'.join(map(str, topo))}",
        packed_cascade, f"launches={n_launches};datapath=packed_mxu")
    us_mg_i, us_mg_c = _lanes(
        rec, f"cascade_popcount_mega_{B}x{'x'.join(map(str, topo))}",
        mega_cascade,
        "launches=1;datapath=popcount_vpu;weight_dma=double_buffered;"
        "fired_planes=vmem_resident")

    # ---- the asserted floor, recorded next to the measurement ---------- #
    sp_mat_i = us_pk_i / us_pc_i
    sp_casc_i = us_cc_i / us_mg_i
    assert sp_mat_i >= SPEEDUP_FLOOR, (
        f"popcount matmul interpret lane below floor: {sp_mat_i:.2f}x")
    assert sp_casc_i >= SPEEDUP_FLOOR, (
        f"mega cascade interpret lane below floor: {sp_casc_i:.2f}x")
    compiled = ""
    if us_pc_c is not None and us_pk_c is not None:
        sp_mat_c = us_pk_c / us_pc_c
        sp_casc_c = us_cc_c / us_mg_c
        assert sp_mat_c >= SPEEDUP_FLOOR, (
            f"popcount matmul compiled lane below floor: {sp_mat_c:.2f}x")
        compiled = (f";speedup_compiled_matmul={sp_mat_c:.2f}x"
                    f";speedup_compiled_cascade={sp_casc_c:.2f}x")
    rec.emit(
        "kernel_popcount_speedup_vs_packed", 0.0,
        f"floor={SPEEDUP_FLOOR:.1f}x;asserted=yes;bit_identity=checked;"
        f"speedup_interpret_matmul={sp_mat_i:.2f}x;"
        f"speedup_interpret_cascade={sp_casc_i:.2f}x{compiled}")


def run():
    rec = Recorder()
    key = jax.random.PRNGKey(0)
    s = jax.random.bernoulli(key, 0.4, (256, 768)).astype(jnp.float32)
    w = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (768, 256)).astype(jnp.int8)
    vth = jnp.zeros((256,), jnp.int32)

    flops = 2 * 256 * 768 * 256
    _lanes(rec, "kernel_cim_matmul_256x768x256",
           lambda interp: cim_ops.cim_matmul(s, w, interpret=interp),
           f"flops={flops};tpu_blocks=128x128x128;"
           f"mxu_aligned=yes;vmem_per_block_kb={(128*128*2*3)//1024}")

    _lanes(rec, "kernel_esam_layer_fused",
           lambda interp: cim_ops.esam_layer(s, w, vth, interpret=interp),
           "fused=mac+if_fire;vmem_resident_vmem=acc128x128xf32")

    req = jax.random.bernoulli(key, 0.4, (16, 128)).astype(jnp.int8)
    _lanes(rec, "kernel_arbiter_16x128_p4",
           lambda interp: arb_ops.arbiter(req, ports=4, interpret=interp),
           "blocked_prefix=32-lane base encoders")

    upd = jax.random.randint(key, (8, 32, 256), -3, 4, jnp.int32)
    _lanes(rec, "kernel_if_neuron_8x32x256",
           lambda interp: if_ops.if_neuron(
               upd, jnp.zeros((256,), jnp.int32), interpret=interp),
           "vmem_resident_vmem=rounds_in_vmem")

    bits = jax.random.bernoulli(key, 0.5, (128, 256)).astype(jnp.int8)
    pre = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.5, (256,)).astype(jnp.int8)
    post = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.2, (128,)).astype(jnp.int8)
    u1 = jax.random.uniform(jax.random.fold_in(key, 4), (128, 256))
    u2 = jax.random.uniform(jax.random.fold_in(key, 5), (128, 256))
    _lanes(rec, "kernel_stdp_128x256",
           lambda interp: stdp_ops.stdp_update(
               bits, pre, post, u1, u2, p_pot=0.2, p_dep=0.1, interpret=interp),
           "layout=column_major_transposed_port")

    uv1 = jax.random.uniform(jax.random.fold_in(key, 6), (256,))
    uv2 = jax.random.uniform(jax.random.fold_in(key, 7), (256,))
    _lanes(rec, "kernel_stdp_column_event_128x256",
           lambda interp: stdp_ops.stdp_column_event(
               bits, jnp.asarray(5, jnp.int32), jnp.asarray(True),
               pre.astype(bool), uv1, uv2, p_pot=0.2, p_dep=0.1,
               interpret=interp),
           "grid=event_column_only;write=aliased_in_place;"
           "rng_draws_per_event=n_in_not_n_in_x_n_out")

    _popcount_comparison(rec, jax.random.fold_in(key, 9))

    rec.write_json(os.environ.get("BENCH_OUT", "BENCH_kernels.json"))


if __name__ == "__main__":
    from repro.launch.env import enable_compilation_cache

    enable_compilation_cache()
    run()
