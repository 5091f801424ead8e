"""Multi-epoch online-learning driver on the fused column-event plane.

The deployment story of Sec 4.4.1: a converted SNN ships with frozen hidden
tiles and adapts its readout on-device through supervised stochastic STDP,
every weight update a column access through the transposable port.  This
driver scales that loop to real batch counts:

* the frozen prefix runs ONCE through a compiled execution plan
  (``EsamNetwork.plan(mode="prefix")`` — the packed fused datapath) and is
  reused across every epoch — the hidden tiles never learn, so their
  activations never change.  The plan's executable and its prepped hidden
  operands are shared along the network line, so a chunked caller handing
  back the network it was given (its readout learned) lowers and preps
  nothing again;
* a split's prefix (cast, pack, plan, unpack) is one jitted step, and an
  epoch (key, shuffle, ``learning.column_event_epoch`` on the transposed
  readout, fault clamp, eval accuracy) another: a chunk dispatches one
  prefix step per split and one epoch step per epoch, and pulls the update
  count and accuracy to the host once per epoch;
* checkpoints are written through ``repro.checkpoint.io`` in the network's
  native ``[n_in, n_out]`` layout, the layout the epoch step takes and
  returns, so they stay compatible with ``EsamNetwork`` consumers and
  resume.

Run the example: ``PYTHONPATH=src python examples/online_learning.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.esam import faults as faults_mod
from repro.core.esam import learning
from repro.core.esam.network import EsamNetwork
from repro.obs.profile import attribute_compiles


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _prefix_step(exec_fn, packed: bool, n_pre: int, params, spikes):
    """The frozen prefix of one split as one program: the {0,1} spikes (any
    dtype) cast and packed onto the wire, the prefix plan's executable
    ``exec_fn`` (shared along the network line, so a line traces this step
    once), and the fired plane unpacked to the last tile's bool input."""
    x = spikes != 0
    if packed:
        x = packing.pack_spikes(x)
    out = exec_fn(params, x)["prefix"]
    if packed:
        out = packing.unpack_spikes(out, n_pre, dtype=jnp.bool_)
    return out


@functools.partial(jax.jit,
                   static_argnames=("p_pot", "p_dep", "shuffle", "interpret"))
def _epoch_step(w_last, pre, labels, eval_pre, eval_labels, key, epoch,
                out_offset, fault_masks, *, p_pot, p_dep, shuffle, interpret):
    """One epoch as one program: the epoch key, the shuffle, the column-event
    epoch on the transposed readout, the fault clamp and fold-back, and the
    eval accuracy.  Takes and returns the readout in the network's
    ``[n_in, n_out]`` layout: (readout, column updates, accuracy)."""
    ep_key = jax.random.fold_in(key, epoch)
    if shuffle:
        # sample draws fold in indices 0..n_samples-1; n_samples is free
        n = pre.shape[0]
        perm = jax.random.permutation(jax.random.fold_in(ep_key, n), n)
        pre, labels = pre[perm], labels[perm]
    bits_t = w_last.T
    # learning events target the deployed readout: the wrong winner is the
    # argmax of the offset-shifted logits, as in EsamNetwork.forward
    if fault_masks is None:
        bits_t, n_upd = learning.column_event_epoch(
            bits_t, pre, labels, ep_key, p_pot=p_pot, p_dep=p_dep,
            out_offset=out_offset, interpret=interpret)
        eval_bits = bits_t
    else:
        # bits_t holds the *programmed* state; the epoch reads and writes
        # the *effective* (clamped) state the array exposes.  Writes that
        # landed (effective bit changed) are folded back into the programmed
        # state — a write into a stuck cell is silently dropped, exactly
        # like the hardware.
        eff0 = faults_mod.clamp_readout_t(bits_t, fault_masks, 4)
        eff, n_upd = learning.column_event_epoch(
            eff0, pre, labels, ep_key, p_pot=p_pot, p_dep=p_dep,
            out_offset=out_offset, interpret=interpret)
        bits_t = jnp.where(eff != eff0, eff, bits_t)
        eval_bits = faults_mod.clamp_readout_t(bits_t, fault_masks, 4)
    logits = (learning.readout_vmem(eval_bits, eval_pre).astype(jnp.float32)
              + out_offset)
    acc = (jnp.argmax(logits, -1) == eval_labels).mean()
    return bits_t.T, n_upd, acc


@dataclasses.dataclass
class OnlineTrainResult:
    network: EsamNetwork        # prefix unchanged, learned last tile swapped in
    accuracy: list[float]       # eval accuracy after each epoch run
    n_updates: list[int]        # column updates per epoch (feeds the cost model)
    start_epoch: int            # 0, or where a resumed run picked up
    epochs_run: int


def _checkpoint_tree(network: EsamNetwork, w_last: jax.Array) -> dict:
    return {"weight_bits": list(network.weight_bits[:-1]) + [w_last]}


def train_online(
    network: EsamNetwork,
    spikes: jax.Array,           # bool[batch, n_in]
    labels: jax.Array,           # int32[batch]
    *,
    epochs: int = 5,
    key: jax.Array | None = None,
    p_pot: float = 0.12,
    p_dep: float = 0.06,
    eval_spikes: jax.Array | None = None,
    eval_labels: jax.Array | None = None,
    shuffle: bool = False,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    interpret: bool | None = None,
    faults: faults_mod.FaultModel | None = None,
    observability=None,
) -> OnlineTrainResult:
    """Supervised-STDP training of the readout tile over multiple epochs.

    Evaluation defaults to the training set when no eval split is given.
    ``shuffle=True`` permutes the sample order per epoch (keyed off the epoch
    key, deterministic).  With ``checkpoint_dir`` set, the full weight list is
    checkpointed every ``checkpoint_every`` epochs (and at the end);
    ``resume=True`` restarts from the latest step found there.

    ``faults`` turns the loop into the *online-learning repair* mitigation:
    the frozen prefix runs through a faulted plan (the hidden activations
    are what a damaged array would actually emit — dead columns included),
    and the learned readout state is clamped through the last tile's fault
    masks between epochs (``faults.clamp_readout_t``: writes into stuck
    cells don't take, reads see the disturb flips), so the per-epoch
    accuracy is the accuracy the faulted hardware would really recover.
    The returned network carries the *programmed* bits — evaluate it under
    the same ``FaultModel`` (``network.plan(..., faults=...)``) for the
    deployed faulted accuracy.

    ``observability`` (an :class:`repro.obs.Observability`) opens a span
    for each step — ``train.plan`` (plan and operands), ``train.prefix``
    (the prefix steps' dispatch), ``train.epoch`` (the epoch step's
    dispatch), ``train.eval`` (the epoch's one pull, which waits for the
    device) — on the profiler's clock, books every compile inside
    the call to the span that triggered it, and books per-epoch wall time,
    column updates, and the latest accuracy into the registry — off by
    default, and inert for the math (spans observe, never perturb).
    """
    from repro.checkpoint import io as ckpt_io

    tracer = observability.tracer if observability is not None else None
    metrics = observability.metrics if observability is not None else None

    def span(name, **args):
        if tracer is None:
            return contextlib.nullcontext()
        return tracer.span(name, cat="train", **args)

    with attribute_compiles(metrics):
        if key is None:
            key = jax.random.PRNGKey(0)
        if (eval_spikes is None) != (eval_labels is None):
            raise ValueError(
                "eval_spikes and eval_labels must be given together")
        # one compiled prefix plan, reused for train and eval splits; with a
        # FaultModel the prefix is the faulted executable (same seed => same
        # masks as any other plan built from this model).  Its operands come
        # from the network line when its hidden arrays were prepped before.
        with span("train.plan"):
            prefix_plan = network.plan(mode="prefix", interpret=interpret,
                                       faults=faults)
            params = prefix_plan._prepare()
        fault_masks = None
        if faults is not None:
            fault_masks = faults.build_masks(network.topology, (4,))

        def run_prefix(x):
            return _prefix_step(prefix_plan._exec, prefix_plan.prefix_packed,
                                network.topology[-2], params, x)

        with span("train.prefix"):
            pre = run_prefix(spikes)
            if eval_spikes is None:
                eval_pre, eval_labels = pre, labels
            else:
                eval_pre = run_prefix(eval_spikes)

        w_last = network.weight_bits[-1]
        start_epoch = 0
        if resume and checkpoint_dir is not None:
            step = ckpt_io.latest_step(checkpoint_dir)
            if step is not None:
                restored, _ = ckpt_io.restore(
                    _checkpoint_tree(network, w_last), checkpoint_dir, step)
                w_last = jnp.asarray(restored["weight_bits"][-1])
                start_epoch = step

        accuracy: list[float] = []
        n_updates: list[int] = []
        for epoch in range(start_epoch, epochs):
            ep_wall0 = time.perf_counter() if observability is not None else 0.0
            with span("train.epoch", epoch=epoch):
                w_last, n, acc = _epoch_step(
                    w_last, pre, labels, eval_pre, eval_labels, key, epoch,
                    network.out_offset, fault_masks, p_pot=float(p_pot),
                    p_dep=float(p_dep), shuffle=bool(shuffle),
                    interpret=interpret)
            with span("train.eval", epoch=epoch):
                n, acc = jax.device_get((n, acc))   # the epoch's one pull
                accuracy.append(float(acc))
                n_updates.append(int(n))
            if metrics is not None:
                metrics.counter(
                    "esam_train_epochs_total",
                    "online-learning epochs completed").inc()
                metrics.counter(
                    "esam_train_column_updates_total",
                    "STDP column updates applied").inc(n_updates[-1])
                metrics.gauge(
                    "esam_train_accuracy",
                    "readout accuracy after the latest epoch").set(accuracy[-1])
                metrics.histogram(
                    "esam_train_epoch_seconds",
                    "wall time per online-learning epoch").observe(
                        time.perf_counter() - ep_wall0)
            at_end = epoch + 1 == epochs
            if checkpoint_dir is not None and (
                at_end or (checkpoint_every
                           and (epoch + 1) % checkpoint_every == 0)
            ):
                ckpt_io.save(
                    _checkpoint_tree(network, w_last), checkpoint_dir,
                    epoch + 1, extra={"accuracy": accuracy[-1],
                                      "n_updates": n_updates[-1]})

        new_net = dataclasses.replace(
            network,
            weight_bits=list(network.weight_bits[:-1]) + [w_last],
        )
        return OnlineTrainResult(
            network=new_net,
            accuracy=accuracy,
            n_updates=n_updates,
            start_epoch=start_epoch,
            epochs_run=len(accuracy),
        )
