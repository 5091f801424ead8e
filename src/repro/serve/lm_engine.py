"""LM serving with slot-based continuous batching.

``Engine`` keeps ``batch_size`` decode slots on the device.  Each ``step()``
prefills queued requests into free slots (one prompt per call, right-padded
to a power-of-two bucket), runs one decode step for every slot, and retires
the requests that finished; sequences join and leave between decode steps.

Every LM family is served (``lm.init_slot_caches``).  The device holds the
caches (per-slot lengths; KV rows, Mamba2 SSM and conv state, xLSTM state,
an encoder-decoder's cross K/V), each slot's current token, its done-mask
and its stop rule.  A decode step returns the tokens it consumed and the ones it
made as one ``[2, B]`` array, and that array is the step's only pull to the
host: the host keeps its own copy of the stop rule and appends tokens with
vectorised numpy, never an ``int()`` per sequence.  Every prefill bucket and
the decode step are compiled ahead of time by ``warmup()``.

With an ``Observability`` handle the engine opens the spans ``lm.admit``,
``lm.prefill``, ``lm.decode``, ``lm.drain`` (the per-step pull) and
``lm.retire``, observes each request's queue wait (admission to slot join)
into ``lm_request_queue_seconds``, and counts ``lm_prefill_tokens_total``,
``lm_decode_steps_total``, ``lm_decode_tokens_total`` and
``lm_host_seconds_total{phase}``.  ``stats()`` gives the same counts without a
handle.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.models import lm
from repro.obs import Observability
from repro.obs.metrics import FINE_BOUNDS

PHASES = ("admit", "prefill", "decode", "drain", "retire")
#: logit positions a request may ask for
CAPTURE = 4
_NULL = contextlib.nullcontext()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # int32[prompt_len]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    #: positions whose logits to keep: 0 is the last prompt position (it
    #: gives output token 0), j >= 1 is decode step j (it gives token j)
    logits_at: tuple = ()
    #: keep the Mamba2 state the request leaves in its slot
    keep_state: bool = False
    # filled by the engine:
    output: Optional[np.ndarray] = None
    #: float32[CAPTURE, V] on the device: row k holds ``logits_at[k]``
    logits: Optional[jax.Array] = None
    #: with ``keep_state``: float32[L_mamba, H, P, N] on the device, the SSM
    #: state after the request's last consumed token
    state: Optional[jax.Array] = None


def _buckets(lo: int, hi: int) -> tuple:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    return tuple(out) + (hi,)


class Engine:
    """Greedy decoding over ``batch_size`` slots of ``max_len`` positions.

    ``serve(requests)`` submits and steps until all are answered; an
    open-loop client calls ``submit()`` and ``step()`` itself, admitting
    arrivals between decode steps.  Prompts longer than the largest bucket,
    or prompt + output longer than ``max_len``, are refused at submit."""

    def __init__(self, params, cfg, *, batch_size: int = 8, max_len: int = 256,
                 min_bucket: int = 16, max_bucket: Optional[int] = None,
                 rules: Optional[shd.ShardingRules] = None,
                 observability: Optional[Observability] = None):
        self.params, self.cfg, self.rules = params, cfg, rules
        self.batch_size, self.max_len = batch_size, max_len
        self.buckets = _buckets(min_bucket, max_bucket or max_len)
        dtype = params["embed"].dtype if cfg.family == "hybrid_moe" else jnp.bfloat16
        b = batch_size
        self._caches = lm.init_slot_caches(cfg, b, max_len, dtype)
        self._has_ssm = hasattr(self._caches.mamba, "ssm")
        self._state = {
            "tok": jnp.zeros((b,), jnp.int32), "live": jnp.zeros((b,), bool),
            "n_gen": jnp.zeros((b,), jnp.int32), "max_new": jnp.zeros((b,), jnp.int32),
            "eos": jnp.full((b,), -1, jnp.int32),
            "cap_pos": jnp.full((b, CAPTURE), -1, jnp.int32),
            "cap": jnp.zeros((b, CAPTURE, cfg.vocab_size), jnp.float32),
        }
        self._prefill_fn = jax.jit(self._prefill_impl, donate_argnums=(1, 2))
        self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        self._exec: dict = {}
        # host side of the slots
        self._queue: collections.deque = collections.deque()
        self._req: list = [None] * b
        self._joined = np.zeros(b, bool)          # joined since the last pull
        self._live = np.zeros(b, bool)            # the device's done-mask, negated
        self._n = np.zeros(b, np.int64)           # tokens known on the host
        self._plen = np.zeros(b, np.int64)
        self._max = np.zeros(b, np.int64)
        self._eos = np.full(b, -1, np.int64)
        self._out = np.zeros((b, max_len), np.int32)
        self._counts = {"requests": 0, "prefill_calls": 0, "prefill_tokens": 0,
                        "prefill_bucket_tokens": 0, "decode_steps": 0,
                        "decode_tokens": 0, "decode_positions": 0, "pulls": 0}
        self._host_s = dict.fromkeys(PHASES, 0.0)
        self._waits: list = []
        obs = observability
        self._tracer = obs.tracer if obs else None
        reg = obs.metrics if obs else None
        self._m = None if reg is None else {
            "queue": reg.histogram("lm_request_queue_seconds",
                                   "admission to slot join", bounds=FINE_BOUNDS),
            "prefill_tokens": reg.counter("lm_prefill_tokens_total",
                                          "prompt tokens prefilled"),
            "decode_steps": reg.counter("lm_decode_steps_total", "decode steps run"),
            "decode_tokens": reg.counter("lm_decode_tokens_total",
                                         "tokens made by decode steps for live slots"),
            **{p: reg.counter("lm_host_seconds_total", "host seconds by phase", phase=p)
               for p in PHASES},
        }

    # ------------------------------------------------------------ device
    def _prefill_impl(self, params, caches, state, tokens, meta):
        """One right-padded prompt into slot ``meta[1]``.  meta: int32
        [length, slot, max_new, eos, capture positions...]."""
        length, slot, max_new, eos = meta[0], meta[1], meta[2], meta[3]
        with shd.use_rules(self.rules):
            logits, one = lm.prefill_rows(params, self.cfg, tokens, length[None])
        caches = lm.insert_slot(caches, one, slot)
        logits = logits[0].astype(jnp.float32)
        tok = jnp.argmax(logits).astype(jnp.int32)
        cap_pos = meta[4:]
        row = jnp.where((cap_pos == 0)[:, None], logits[None], 0.0)
        state = dict(
            state, tok=state["tok"].at[slot].set(tok),
            live=state["live"].at[slot].set((max_new > 1) & (tok != eos)),
            n_gen=state["n_gen"].at[slot].set(1),
            max_new=state["max_new"].at[slot].set(max_new),
            eos=state["eos"].at[slot].set(eos),
            cap_pos=state["cap_pos"].at[slot].set(cap_pos),
            cap=state["cap"].at[slot].set(row))
        return caches, state

    def _decode_impl(self, params, caches, state):
        """One token for every live slot; returns the [2, B] tokens consumed
        and made (the only array the host pulls)."""
        live, tok = state["live"], state["tok"]
        with shd.use_rules(self.rules):
            logits, new = lm.decode_step(params, self.cfg, tok[:, None], caches)
        new = new._replace(lengths=jnp.where(live, new.lengths, caches.lengths))
        logits = logits.astype(jnp.float32)
        nxt = jnp.where(live, jnp.argmax(logits, -1).astype(jnp.int32), tok)
        n_gen = state["n_gen"]
        hit = (state["cap_pos"] == n_gen[:, None]) & live[:, None]
        cap = jnp.where(hit[..., None], logits[:, None, :], state["cap"])
        n_gen = n_gen + live
        done = (n_gen >= state["max_new"]) | (nxt == state["eos"])
        state = dict(state, tok=nxt, live=live & ~done, n_gen=n_gen, cap=cap)
        return new, state, jnp.stack([tok, nxt])

    def _compiled(self, key):
        if key not in self._exec:
            if key == "decode":
                low = self._decode_fn.lower(self.params, self._caches, self._state)
            elif key == "row":
                low = jax.jit(lambda cap, i: cap[i]).lower(
                    self._state["cap"], jax.ShapeDtypeStruct((), jnp.int32))
            elif key == "state":
                low = jax.jit(lambda ssm, i: ssm[:, i]).lower(
                    self._caches.mamba.ssm, jax.ShapeDtypeStruct((), jnp.int32))
            else:
                tok = jax.ShapeDtypeStruct((1, key), jnp.int32)
                meta = jax.ShapeDtypeStruct((4 + CAPTURE,), jnp.int32)
                low = self._prefill_fn.lower(self.params, self._caches, self._state, tok, meta)
            self._exec[key] = low.compile()
        return self._exec[key]

    def warmup(self) -> None:
        """Compile every prefill bucket and the decode step ahead of time."""
        for key in self.buckets + ("decode", "row") + ("state",) * self._has_ssm:
            self._compiled(key)

    # ------------------------------------------------------------ host
    def _span(self, name):
        return self._tracer.span(name) if self._tracer is not None else _NULL

    def _book(self, phase, t0):
        dt = time.perf_counter() - t0
        self._host_s[phase] += dt
        if self._m is not None:
            self._m[phase].inc(dt)

    def submit(self, requests) -> None:
        now = time.perf_counter()
        for r in requests:
            n = len(r.prompt)
            if not 0 < n <= self.buckets[-1] or n + r.max_new_tokens > self.max_len:
                raise ValueError(f"prompt {n} + {r.max_new_tokens} new tokens do not "
                                 f"fit a slot (buckets {self.buckets}, {self.max_len})")
            if len(r.logits_at) > CAPTURE:
                raise ValueError(f"at most {CAPTURE} logit positions a request")
            if r.keep_state and not self._has_ssm:
                raise ValueError(f"family {self.cfg.family!r} keeps no Mamba2 state")
            self._queue.append((r, now))

    def queue_depth(self) -> int:
        """Requests waiting for a slot or still decoding."""
        return len(self._queue) + sum(r is not None for r in self._req)

    def _admit(self) -> None:
        free = [i for i, r in enumerate(self._req) if r is None]
        while self._queue and free:
            t0 = time.perf_counter()
            with self._span("lm.admit"):
                r, t_sub = self._queue.popleft()
                slot, n = free.pop(0), len(r.prompt)
                bucket = next(b for b in self.buckets if b >= n)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = r.prompt
                eos = -1 if r.eos_id is None else int(r.eos_id)
                cap = list(r.logits_at) + [-1] * (CAPTURE - len(r.logits_at))
                meta = np.asarray([n, slot, r.max_new_tokens, eos] + cap, np.int32)
                self._waits.append(t0 - t_sub)
                if self._m is not None:
                    self._m["queue"].observe(t0 - t_sub)
                    self._m["prefill_tokens"].inc(n)
            self._book("admit", t0)
            t0 = time.perf_counter()
            with self._span("lm.prefill"):
                self._caches, self._state = self._compiled(bucket)(
                    self.params, self._caches, self._state, tokens, meta)
            self._book("prefill", t0)
            self._req[slot] = r
            self._joined[slot] = True
            self._n[slot], self._max[slot], self._eos[slot] = 0, r.max_new_tokens, eos
            self._plen[slot] = n
            c = self._counts
            c["prefill_calls"] += 1
            c["prefill_tokens"] += n
            c["prefill_bucket_tokens"] += bucket

    def step(self) -> int:
        """Admit into free slots, decode one step, retire what finished.
        Returns the requests retired."""
        self._admit()
        if not (self._joined.any() or self._live.any()):
            return 0
        t0 = time.perf_counter()
        with self._span("lm.decode"):
            self._caches, self._state, toks = self._compiled("decode")(
                self.params, self._caches, self._state)
        self._book("decode", t0)
        t0 = time.perf_counter()
        with self._span("lm.drain"):
            toks = jax.device_get(toks)
        self._book("drain", t0)
        t0 = time.perf_counter()
        with self._span("lm.retire"):
            done = self._take(toks)
        self._book("retire", t0)
        return done

    def _take(self, toks: np.ndarray) -> int:
        """Append the pulled tokens, mirror the device's stop rule, retire."""
        t_in, t_out = toks[0], toks[1]
        j = np.flatnonzero(self._joined)
        self._out[j, 0] = t_in[j]
        self._n[j] = 1
        self._live[j] = (self._max[j] > 1) & (t_in[j] != self._eos[j])
        self._joined[:] = False
        live = np.flatnonzero(self._live)
        positions = int((self._plen[live] + self._n[live] - 1).sum())
        self._out[live, self._n[live]] = t_out[live]
        self._n[live] += 1
        stop = (self._n[live] >= self._max[live]) | (t_out[live] == self._eos[live])
        self._live[live[stop]] = False
        c = self._counts
        c["decode_steps"] += 1
        c["decode_tokens"] += live.size
        c["decode_positions"] += positions
        c["pulls"] += 1
        if self._m is not None:
            self._m["decode_steps"].inc()
            self._m["decode_tokens"].inc(live.size)
        gone = [i for i, r in enumerate(self._req) if r is not None and not self._live[i]]
        for i in gone:
            r = self._req[i]
            r.output = self._out[i, : self._n[i]].copy()
            if r.logits_at:
                r.logits = self._compiled("row")(self._state["cap"], np.int32(i))
            if r.keep_state:
                r.state = self._compiled("state")(self._caches.mamba.ssm, np.int32(i))
            self._req[i] = None
        c["requests"] += len(gone)
        return len(gone)

    def serve(self, requests: Optional[list] = None) -> Optional[list]:
        """Submit ``requests`` (if any) and step until every admitted
        request is answered."""
        if requests:
            self.submit(requests)
        while self.queue_depth():
            self.step()
        return requests

    def close(self) -> None:
        """Drop the device caches, slot state, executables and weights (the
        jitted methods hold the engine, so its collection may come late)."""
        self.params = self._caches = self._state = None
        self._exec.clear()

    def stats(self) -> dict:
        """Counts since construction: requests retired, prefill calls, real
        and bucket-padded prompt tokens, decode steps, tokens made by decode
        steps for live slots, the tokens those slots held before each step
        (summed), device-to-host pulls, host seconds by phase
        (``admit``, ``prefill`` and ``decode`` dispatch, ``drain`` = the
        pull, ``retire``) and every request's queue wait in seconds."""
        return dict(self._counts, host_s=dict(self._host_s),
                    queue_wait_s=np.asarray(self._waits, np.float64))
