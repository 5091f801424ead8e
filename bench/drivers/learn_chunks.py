"""Online learning in chunks: ``train_online`` (one epoch of supervised
STDP on the readout) runs on successive chunks of labelled digit samples,
each chunk starting from the readout the previous one learned, until the
window has run.

Traffic parameters: ``chunk`` (samples per call), ``reference_chunks`` (the
first chunks, run in set-up through the same call and the same object, that
the reference follows), ``pool_samples_per_s`` (samples made before the
window: this rate times the window, plus the reference chunks; a faster
program reuses their inputs), ``flip_noise``.

The reference also follows the window's last chunk, the one that crosses
its close, from the readout the program held just before it: the plan built
for a network inside the window, after the readout has drifted, is checked
as well as those of set-up.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import gen
import reference as ref
import serving
import system
import work
from harness import Check

KEY_STREAM = 100


class Driver:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 clock=time.perf_counter, sleep=time.sleep):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.clock = clock
        # what train_online is, swappable for the fault tests
        from repro.train.online import train_online

        self.train = train_online

    def _step(self, net, c: int):
        n = self.chunk
        a = (c * n) % len(self.y)
        res = self.train(net, self.x[a:a + n], self.y[a:a + n], epochs=1,
                         key=system.device_key(self.seed, KEY_STREAM + c),
                         p_pot=self.p_pot, p_dep=self.p_dep)
        return res

    def setup(self) -> None:
        cfg, tr = self.cell.config, self.cell.traffic
        self.network = system.Network(cfg, self.seed)
        self.chunk = int(tr["chunk"])
        self.n_ref = int(tr["reference_chunks"])
        self.p_pot = float(cfg["learning"]["p_pot"])
        self.p_dep = float(cfg["learning"]["p_dep"])
        n_chunks = self.n_ref + max(1, int(np.ceil(
            float(tr["pool_samples_per_s"]) * self.seconds / self.chunk)))
        t0 = time.perf_counter()
        self.x, self.y = gen.digit_spikes(
            n_chunks * self.chunk, self.seed,
            flip_noise=float(tr.get("flip_noise", 0.02)))
        t1 = time.perf_counter()
        # the first chunks: set-up (they compile) and what the reference
        # follows; their readout bits and update counts are kept on the host
        net = self.network.net
        self.ref_bits, self.ref_updates = [], []
        for c in range(self.n_ref):
            res = self._step(net, c)
            net = res.network
            self.ref_bits.append(np.asarray(net.weight_bits[-1]).T.copy())
            self.ref_updates.append(int(res.n_updates[0]))
        self.net = net
        print("set-up s: samples %.3f, first chunks %.3f"
              % (t1 - t0, time.perf_counter() - t1), file=sys.stderr,
              flush=True)

    def window(self) -> dict:
        c = self.n_ref
        samples = updates = 0
        calls = []
        t0 = self.clock()
        while True:
            before = self.net.weight_bits[-1]
            t_call = self.clock()
            with serving.annotate(self.trace, "bench.train_online"):
                res = self._step(self.net, c)
            calls.append(self.clock() - t_call)
            self.net = res.network
            samples += self.chunk
            updates += int(res.n_updates[0])
            c += 1
            t_end = self.clock()
            if t_end - t0 >= self.seconds:
                break
        window_s = t_end - t0
        # the last chunk, for the reference: its readout before and after,
        # and its update count, on the host
        self.last = (c - 1, np.asarray(before).T.copy(),
                     np.asarray(self.net.weight_bits[-1]).T.copy(),
                     int(res.n_updates[0]))
        topo = self.network.topology
        return {
            "window_s": window_s,
            "attempted": samples,
            "failed": 0,
            "end_to_end": {"learn_samples_per_s": samples / window_s},
            "record": {
                "completed": samples,
                "macs": samples * work.learn_macs_per_sample(topo),
                "column_updates": updates,
                "chunks": c - self.n_ref,
                "call_s": calls,
            },
        }

    def release(self) -> None:
        self.net = None

    def _ref_chunk(self, bits_t, c: int, dtype: str):
        """The reference's chunk ``c`` from readout ``bits_t``
        ({0,1}[n_cls, n_in]): (readout bits, update count) after it."""
        import jax

        nw = self.network
        n = self.chunk
        a = (c * n) % len(self.y)
        pre = ref.hidden_spikes(nw.bits, nw.vth, self.x[a:a + n])
        key = jax.random.fold_in(
            system.device_key(self.seed, KEY_STREAM + c), 0)
        u = ref.stdp_uniforms(key, n, bits_t.shape[1])
        return ref.stdp_epoch(bits_t, pre, self.y[a:a + n], u, self.p_pot,
                              self.p_dep, nw.out_offset, dtype)

    def _reference(self, dtype: str):
        """The reference followed through the first chunks from the seed's
        weights, then through the window's last chunk from the readout the
        program held before it: (readout bits, update count) after each."""
        bits_t = self.network.bits[-1].T.astype(np.int8)
        out = []
        for c in range(self.n_ref):
            bits_t, n_upd = self._ref_chunk(bits_t, c, dtype)
            out.append((bits_t, n_upd))
        c, before, _, _ = self.last
        out.append(self._ref_chunk(before.astype(np.int8), c, dtype))
        return out

    def _compare(self, got) -> list:
        """Worst gap over the first chunks and the window's last: readout
        bits that differ, and the difference in update counts."""
        want = self._reference("exact")
        bits = max(int((np.asarray(g[0]) != w[0]).sum())
                   for g, w in zip(got, want))
        upd = max(abs(int(g[1]) - w[1]) for g, w in zip(got, want))
        lim = self.cell.config["limits"]
        return [Check("readout_bits_gap", float(bits),
                      lim["readout_bits_gap"]),
                Check("updates_gap", float(upd), lim["updates_gap"])]

    def checks(self) -> list:
        _, _, after, n_upd = self.last
        return self._compare(list(zip(self.ref_bits, self.ref_updates))
                             + [(after, n_upd)])

    def control(self) -> list:
        """The control: the bfloat16 reference in the program's place."""
        return self._compare(self._reference("bf16"))
