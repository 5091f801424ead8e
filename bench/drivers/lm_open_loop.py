"""Open-loop LM serving: chat requests arrive on a seeded Poisson schedule
and are admitted between the engine's decode steps, whatever it does.

Traffic parameters: ``rate_hz`` (offered rate), ``prompt`` and ``output``
(lognormal lengths: ``median``, ``sigma``, clipped to ``min`` .. ``max``),
``length_seed`` (the (prompt, output) pairs are drawn from it, so every run
replays the same lengths; arrivals and token ids come from the run's seed,
ids uniform over the configuration's vocabulary) and ``check_sample``.
Decoding is greedy with no end-of-sequence id.  Every request due in the
window is served to completion after the window closes, and every one
counts in the tail.  Latency runs from a request's nominal arrival to the
moment its last token is on the host.  The cell reports no request rate:
below capacity it is the offered rate, and the drain after the last
arrival (seconds of decoding, set by where the seed puts the long
answers) would move it by percents from run to run.

The engine is ``serve/lm_engine.Engine`` with the configuration's slots and
buckets; the weights are random, drawn on the device from the seed by the
benchmark in the published layout (``lm_weights``) and handed to the program
through a converter into its stacked layout.  The comparison that decides
``correct`` follows a seed-drawn sample of answered requests (always holding
the one with the longest prompt plus output), against ``bench/lm_reference.py``
teacher-forced on the engine's own tokens, on the chip after the window, with
the weights drawn again there one layer at a time:

- the engine's logits at the last prompt position and at decode steps 1, the
  middle one and the last: ``logits_rel_gap_p50`` and ``logits_rel_gap_max``
  (per position, max |gap| over the vocabulary / RMS of the reference's);
- the SSM state after the request's last consumed token, in the
  ``SLOW_HEADS`` heads of the first Mamba2 layer that decay slowest (the
  smallest nominal rate softplus(dt_bias)·exp(A_log)): ``ssm_state_rel_gap``,
  the largest over the sample of ||gap|| / ||reference|| over those heads.
  The first layer's input is the embedding, the same on both sides, and a
  slow head holds a token for hundreds of steps, so a state kept coarser
  than stated piles its rounding up there, while the activations' rounding
  does not grow with the heads' memory.

The control (``control()``) puts the reference in the program's place, with
what the configuration states one step lower: the state check reads the
reference with its Mamba2 state in bfloat16 and nothing else changed, the
logits checks read it with every stated precision one step lower (weights
and activations into matmuls in float8 e4m3, the state in bfloat16).
"""

from __future__ import annotations

import sys
import time

import numpy as np

import gen
import lm_reference
import lm_weights
import serving
from harness import Check

from repro.configs.base import ModelConfig
from repro.serve.lm_engine import Engine, Request


def model_config(c: dict) -> ModelConfig:
    """The program's configuration for a configuration file (published
    ``config.json`` keys, cut as its ``reduced`` says)."""
    for key, want in (("mamba_d_conv", 4), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"), ("attention_bias", False),
                      ("mamba_proj_bias", False), ("mamba_conv_bias", True)):
        if c[key] != want:
            raise ValueError(f"{key}={c[key]!r}: the program has only {want!r}")
    heads = int(c["num_attention_heads"])
    return ModelConfig(
        name=c["name"], family="hybrid_moe", n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), n_heads=heads,
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["hidden_size"]) // heads, d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        n_experts=int(c["published"]["num_local_experts"]),
        n_experts_held=int(c["num_local_experts"]), expert_offset=int(c["expert_offset"]),
        top_k=int(c["num_experts_per_tok"]),
        shared_expert_ff=int(c["shared_intermediate_size"]),
        ssm_state=int(c["mamba_d_state"]), ssm_heads=int(c["mamba_n_heads"]),
        ssm_head_dim=int(c["mamba_d_head"]), ssm_groups=int(c["mamba_n_groups"]),
        ssm_expand=int(c["mamba_expand"]), ssm_chunk=int(c["mamba_chunk_size"]),
        layer_types=tuple(c["layer_types"]),
        position_embedding=c["position_embedding_type"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]), norm_eps=float(c["rms_norm_eps"]),
        tied_embeddings=bool(c["tie_word_embeddings"]), remat=False)


def lognormal_lengths(spec: dict, n: int, g: np.random.Generator) -> np.ndarray:
    x = np.exp(np.log(float(spec["median"])) + float(spec["sigma"]) * g.standard_normal(n))
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def make_weights(cfg, config: dict, seed: int):
    """Every weight of ``cfg``: the published tensors drawn on the device
    (``lm_weights``) and converted into the program's stacked layout."""
    from repro.models import lm, params as pm

    return lm_weights.to_program(config, seed, pm.shape_structs(lm.model_specs(cfg)))


def drive(engine, objs, arrivals, *, clock=time.perf_counter,
          sleep=time.sleep, trace=False):
    """Submit ``objs[i]`` at ``t0 + arrivals[i]`` between decode steps and
    step until every one is answered.  Returns (t0, wake lags, step
    durations), in s."""
    n = len(objs)
    lags, calls = [], []
    i = 0
    woke_for = -np.inf
    t0 = clock()
    while True:
        now = max(clock() - t0, woke_for)
        j = int(np.searchsorted(arrivals, now, side="right"))
        if j > i:
            with serving.annotate(trace, "bench.submit"):
                engine.submit(objs[i:j])
            i = j
        if engine.queue_depth() == 0:
            if i >= n:
                break
            wait = arrivals[i] - (clock() - t0)
            if wait > 0:
                with serving.annotate(trace, "bench.idle_wait"):
                    sleep(wait)
                lags.append((clock() - t0) - arrivals[i])
            woke_for = arrivals[i]
            continue
        t_call = clock()
        with serving.annotate(trace, "bench.step"):
            engine.step()
        calls.append(clock() - t_call)
    return t0, np.asarray(lags, np.float64), calls


#: heads of the first Mamba2 layer whose state ``ssm_state_rel_gap`` reads
SLOW_HEADS = 4


def slow_heads(config: dict, seed: int, n: int = SLOW_HEADS) -> np.ndarray:
    """The ``n`` heads of the first Mamba2 layer with the smallest nominal
    decay rate softplus(dt_bias)·exp(A_log)."""
    i = lm_weights.kinds(config).index("mamba")
    dt_bias = np.asarray(lm_weights.draw_tensor(config, seed, i, "mamba.dt_bias"))
    a_log = np.asarray(lm_weights.draw_tensor(config, seed, i, "mamba.A_log"))
    return np.argsort(np.logaddexp(0.0, dt_bias) * np.exp(a_log))[:n]


class Driver:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 clock=time.perf_counter, sleep=time.sleep):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.clock, self.sleep = clock, sleep

    def setup(self) -> None:
        cfg_file = self.cell.config
        e = cfg_file["engine"]
        t = [time.perf_counter()]
        self.cfg = model_config(cfg_file)
        self.params = make_weights(self.cfg, cfg_file, self.seed)
        self.engine = Engine(self.params, self.cfg, batch_size=int(e["slots"]),
                             max_len=int(e["max_len"]), min_bucket=int(e["min_bucket"]),
                             max_bucket=int(e["max_bucket"]))
        t.append(time.perf_counter())
        self.make_requests(float(self.cell.traffic["rate_hz"]), self.seed)
        t.append(time.perf_counter())
        self.engine.warmup()
        # one real call of every executable: each bucket, then decode steps
        warm = gen.rng(self.seed, 9)
        self.engine.serve([Request(prompt=warm.integers(0, self.cfg.vocab_size, b,
                                                        dtype=np.int32),
                                   max_new_tokens=2, logits_at=(0, 1))
                           for b in self.engine.buckets])
        t.append(time.perf_counter())
        print("set-up s: weights and engine %.3f, requests %.3f, warm-up %.3f"
              % tuple(b - a for a, b in zip(t, t[1:])), file=sys.stderr, flush=True)

    def make_requests(self, rate_hz: float, seed: int) -> None:
        """The window's arrivals, lengths, prompts and checked sample."""
        tr = self.cell.traffic
        self.arrivals = gen.poisson_arrivals(rate_hz, self.seconds, seed)
        n = len(self.arrivals)
        g = gen.rng(int(tr["length_seed"]), 0)
        self.plen = lognormal_lengths(tr["prompt"], n, g)
        self.olen = lognormal_lengths(tr["output"], n, g)
        ids = gen.rng(seed, 7).integers(0, self.cfg.vocab_size, int(self.plen.sum()),
                                        dtype=np.int32)
        prompts = np.split(ids, np.cumsum(self.plen)[:-1])
        longest = int(np.argmax(self.plen + self.olen))
        rest = np.delete(np.arange(n), longest)
        k = min(int(tr["check_sample"]) - 1, rest.size)
        self.sample = np.r_[longest, gen.rng(seed, 8).choice(rest, k, replace=False)]
        cls = serving.stamped(Request, "output", self.clock)
        self.objs = [cls(prompt=prompts[i], max_new_tokens=int(self.olen[i]))
                     for i in range(n)]
        for i in self.sample:
            o = int(self.olen[i])
            self.objs[i].logits_at = (0, 1, o // 2, o - 1)
            self.objs[i].keep_state = True
        self._refs = {}

    def window(self) -> dict:
        before = self.engine.stats()
        t0, lags, calls = drive(self.engine, self.objs, self.arrivals, clock=self.clock,
                                sleep=self.sleep, trace=self.trace)
        done_at = np.array([np.nan if r.done_at is None else r.done_at for r in self.objs])
        self.done = ~np.isnan(done_at)
        n_done = int(self.done.sum())
        t_end = float(np.nanmax(done_at)) if n_done else self.clock()
        window_s = t_end - t0
        lat_ms = (done_at[self.done] - (t0 + self.arrivals[self.done])) * 1e3
        if n_done:
            print("window latency ms: p50 %.3f p90 %.3f p99 %.3f max %.3f"
                  % tuple(np.percentile(lat_ms, [50, 90, 99, 100])), flush=True)
        after = self.engine.stats()
        lm = {k: after[k] - before[k] for k in (
            "requests", "prefill_calls", "prefill_tokens", "prefill_bucket_tokens",
            "decode_steps", "decode_tokens", "decode_positions", "pulls")}
        lm["host_s"] = {k: v - before["host_s"][k] for k, v in after["host_s"].items()}
        lm["queue_wait_s"] = after["queue_wait_s"][before["queue_wait_s"].size:]
        lm["prefill_lengths"] = self.plen[self.done].tolist()
        return {
            "window_s": window_s,
            "attempted": len(self.objs),
            "failed": len(self.objs) - n_done,
            "end_to_end": {"latency_p90_ms": float(np.percentile(lat_ms, 90.0))
                           if n_done else float("nan")},
            "record": {"completed": n_done, "lm": lm, "wake_lags_s": lags,
                       "call_s": calls},
        }

    def release(self) -> None:
        """Free the engine's caches and the program's weights: the reference
        draws its own."""
        self.engine.close()
        self.engine = self.params = None

    def _reference(self, precisions) -> dict:
        """Per sampled request and precision, the reference's logits at its
        compared positions and its Mamba2 states, teacher-forced on the
        engine's own tokens."""
        todo = [p for p in precisions if p not in self._refs]
        if todo:
            c, seed = self.cell.config, self.seed
            idx = [int(i) for i in self.sample if self.done[i]]
            seqs = [np.r_[self.objs[i].prompt, self.objs[i].output[:-1]] for i in idx]
            pos = [int(self.plen[i]) - 1 + np.asarray(self.objs[i].logits_at) for i in idx]
            got = lm_reference.run(lambda layer: lm_weights.draw_layer(c, seed, layer),
                                   lm_weights.draw_embed(c, seed),
                                   lm_weights.draw_final_norm(c, seed),
                                   lm_reference.sizes(c), seqs, pos, tuple(todo),
                                   width=int(c["engine"]["max_len"]))
            for p in todo:
                self._refs[p] = dict(zip(idx, got[p]))
        return {p: self._refs[p] for p in precisions}

    def _program(self) -> dict:
        """Per sampled request: the engine's logits at its compared positions
        and its Mamba2 states, in the reference's layout [L, H, N, P]."""
        return {i: (np.asarray(self.objs[i].logits)[: len(self.objs[i].logits_at)],
                    np.asarray(self.objs[i].state).swapaxes(-1, -2))
                for i in self._refs[lm_reference.EXACT]}

    def _compare(self, got: dict, want: dict, what=("logits", "state"),
                 tag: str = "program") -> list:
        """Logits: max |got - want| / RMS(want) per compared position, its
        median and its largest; state: the first Mamba2 layer's relative gap,
        its largest over the sample."""
        lim = self.cell.config["limits"]
        out = []
        if "logits" in what:
            gaps = np.concatenate([lm_reference.rel_gaps(got[i][0], want[i][0])
                                   for i in want]) if want else np.zeros(0)
            print(f"{tag} logits rel gaps: " + " ".join(f"{g:.4g}" for g in gaps),
                  file=sys.stderr, flush=True)
            out += [Check("logits_rel_gap_p50",
                          float(np.median(gaps)) if gaps.size else float("inf"),
                          lim["logits_rel_gap_p50"]),
                    Check("logits_rel_gap_max",
                          float(gaps.max()) if gaps.size else float("inf"),
                          lim["logits_rel_gap_max"])]
        if "state" in what:
            slow = slow_heads(self.cell.config, self.seed)
            layers = np.stack([lm_reference.state_gaps(got[i][1], want[i][1])
                               for i in want]) if want else np.full((1, 1), np.inf)
            gaps = np.array([lm_reference.state_gaps(got[i][1][:1, slow], want[i][1][:1, slow])[0]
                             for i in want]) if want else np.full(1, np.inf)
            print(f"{tag} state rel gaps of the slow heads: "
                  + " ".join(f"{g:.4g}" for g in gaps)
                  + "; every head, by Mamba2 layer (max over the sample): "
                  + " ".join(f"{g:.4g}" for g in layers.max(axis=0)),
                  file=sys.stderr, flush=True)
            out.append(Check("ssm_state_rel_gap", float(gaps.max()),
                             lim["ssm_state_rel_gap"]))
        return out

    def checks(self) -> list:
        want = self._reference((lm_reference.EXACT,))[lm_reference.EXACT]
        return self._compare(self._program(), want) + [
            Check("unanswered", float((~self.done).sum()), 0.0)]

    def control(self) -> list:
        """The control's readings: the reference in the program's place, with
        its Mamba2 state in bfloat16 for the state check, and with every
        stated precision one step lower for the logits checks, against the
        float32 reference."""
        exact = lm_reference.EXACT
        state = lm_reference.Precision(state="bfloat16")
        every = lm_reference.Precision(state="bfloat16", act="float8_e4m3",
                                       weight="float8_e4m3")
        ref = self._reference((exact, state, every))
        # every reading of both, for the record; the checks as stated above
        self._compare(ref[state], ref[exact], ("logits",), "control(state)")
        self._compare(ref[every], ref[exact], ("state",), "control(every)")
        return (self._compare(ref[every], ref[exact], ("logits",), "control(every)")
                + self._compare(ref[state], ref[exact], ("state",), "control(state)"))
