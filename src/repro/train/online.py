"""Multi-epoch online-learning driver on the fused column-event plane.

The deployment story of Sec 4.4.1: a converted SNN ships with frozen hidden
tiles and adapts its readout on-device through supervised stochastic STDP,
every weight update a column access through the transposable port.  This
driver scales that loop to real batch counts:

* the frozen prefix runs ONCE through a compiled execution plan
  (``EsamNetwork.plan(mode="prefix")`` — the packed fused datapath) and is
  reused across every epoch — the hidden tiles never learn, so their
  activations never change;
* the last-layer bits stay transposed-resident (``{0,1}[n_out, n_in]``)
  across epochs, fed straight back into ``learning.column_event_epoch``
  whose donated carry updates them in place;
* accuracy is tracked per epoch from the resident layout (one readout
  matvec, no re-transposition), and checkpoints are written through
  ``repro.checkpoint.io`` in the network's native ``[n_in, n_out]`` layout so
  they stay compatible with ``EsamNetwork`` consumers and resume.

Run the example: ``PYTHONPATH=src python examples/online_learning.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core.esam import faults as faults_mod
from repro.core.esam import learning
from repro.core.esam.network import EsamNetwork
from repro.obs.profile import attribute_compiles


@jax.jit
def _readout_accuracy(bits_t, pre, labels, out_offset):
    """argmax accuracy of the transposed-resident readout on (pre, labels)."""
    logits = learning.readout_vmem(bits_t, pre).astype(jnp.float32) + out_offset
    return (jnp.argmax(logits, -1) == labels).mean()


@dataclasses.dataclass
class OnlineTrainResult:
    network: EsamNetwork        # prefix unchanged, learned last tile swapped in
    accuracy: list[float]       # eval accuracy after each epoch run
    n_updates: list[int]        # column updates per epoch (feeds the cost model)
    start_epoch: int            # 0, or where a resumed run picked up
    epochs_run: int


def _checkpoint_tree(network: EsamNetwork, bits_t: jax.Array) -> dict:
    return {"weight_bits": list(network.weight_bits[:-1]) + [bits_t.T]}


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
    for each step — ``train.plan``, ``train.prefix``, ``train.epoch``,
    ``train.eval`` — on the profiler's clock, books every compile inside
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
        spikes = jnp.asarray(spikes).astype(bool)
        labels = jnp.asarray(labels)
        # one compiled prefix plan, reused for train and eval splits; with a
        # FaultModel the prefix is the faulted executable (same seed => same
        # masks as any other plan built from this model)
        with span("train.plan"):
            prefix_plan = network.plan(mode="prefix", interpret=interpret,
                                       faults=faults)
        n_pre = network.topology[-2]
        fault_masks = None
        if faults is not None:
            fault_masks = faults.build_masks(network.topology, (4,))

        def clamp(bt):
            if fault_masks is None:
                return bt
            return faults_mod.clamp_readout_t(bt, fault_masks, 4)

        def run_prefix(x):
            out = prefix_plan(x).prefix
            if prefix_plan.prefix_packed:
                from repro.core import packing

                out = packing.unpack_spikes(out, n_pre, dtype=jnp.bool_)
            return out

        with span("train.prefix"):
            pre = run_prefix(spikes)
            if eval_spikes is None:
                eval_pre, eval_labels = pre, labels
            else:
                eval_pre = run_prefix(jnp.asarray(eval_spikes).astype(bool))
                eval_labels = jnp.asarray(eval_labels)

        bits_t = jnp.asarray(network.weight_bits[-1]).T
        start_epoch = 0
        if resume and checkpoint_dir is not None:
            step = ckpt_io.latest_step(checkpoint_dir)
            if step is not None:
                restored, _ = ckpt_io.restore(
                    _checkpoint_tree(network, bits_t), checkpoint_dir, step)
                bits_t = jnp.asarray(restored["weight_bits"][-1]).T
                start_epoch = step

        n_samples = int(spikes.shape[0])
        accuracy: list[float] = []
        n_updates: list[int] = []
        for epoch in range(start_epoch, epochs):
            ep_wall0 = time.perf_counter() if observability is not None else 0.0
            ep_key = jax.random.fold_in(key, epoch)
            if shuffle:
                # sample draws fold in indices 0..n_samples-1; n_samples is free
                perm = jax.random.permutation(
                    jax.random.fold_in(ep_key, n_samples), n_samples)
                x_e, y_e = pre[perm], labels[perm]
            else:
                x_e, y_e = pre, labels
            # learning events target the deployed readout: the wrong winner
            # is the argmax of the offset-shifted logits, matching
            # _readout_accuracy and EsamNetwork.forward
            with span("train.epoch", epoch=epoch):
                if fault_masks is None:
                    bits_t, n = learning.column_event_epoch(
                        bits_t, x_e, y_e, ep_key,
                        p_pot=float(p_pot), p_dep=float(p_dep),
                        out_offset=network.out_offset, interpret=interpret)
                    eval_bits = bits_t
                else:
                    # bits_t holds the *programmed* state; the epoch reads and
                    # writes the *effective* (clamped) state the array exposes.
                    # Writes that landed (effective bit changed) are folded
                    # back into the programmed state — a write into a stuck
                    # cell is silently dropped, exactly like the hardware.
                    # clamp() is a pure function of static masks, so
                    # recomputing it after the donated epoch call is exact.
                    eff, n = learning.column_event_epoch(
                        clamp(bits_t), x_e, y_e, ep_key,
                        p_pot=float(p_pot), p_dep=float(p_dep),
                        out_offset=network.out_offset, interpret=interpret)
                    bits_t = jnp.where(eff != clamp(bits_t), eff, bits_t)
                    eval_bits = clamp(bits_t)
            with span("train.eval", epoch=epoch):
                acc = _readout_accuracy(
                    eval_bits, eval_pre, eval_labels, network.out_offset)
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
                    _checkpoint_tree(network, bits_t), checkpoint_dir,
                    epoch + 1, extra={"accuracy": accuracy[-1],
                                      "n_updates": n_updates[-1]})

        new_net = dataclasses.replace(
            network,
            weight_bits=list(network.weight_bits[:-1]) + [bits_t.T],
        )
        return OnlineTrainResult(
            network=new_net,
            accuracy=accuracy,
            n_updates=n_updates,
            start_epoch=start_epoch,
            epochs_run=len(accuracy),
        )
