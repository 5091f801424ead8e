"""Seeded request generators: digit spike planes, rate-coded event streams,
open-loop arrival schedules.

These are the benchmark's own copies of the program's data generators
(``repro.data.digits``, ``repro.data.events``), vectorised so that a whole
window's requests are made before the window opens, and kept here so that a
change to the program's data code cannot move the yardstick.  The digits
follow ``make_digits`` (7x5 glyphs upscaled x3, dilation with probability
1/2, +-2 px jitter, 2% pixel flips, corners cropped 784 -> 768); the streams
follow ``rate_encode`` (spike_t ~ Bernoulli(gain * pixel)).  The draws are
the benchmark's own, so the samples differ from the program's generators'
for the same seed; their distribution is the same, with probabilities
drawn to 2^-16.

Every function is deterministic in its ``seed`` (any non-negative int,
including ones wider than 32 bits).
"""

from __future__ import annotations

import numpy as np

IMG = 28
N_IN = 768
LANE_BITS = 32

_GLYPHS = {
    0: ["01110", "10001", "10001", "10001", "10001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    3: ["01110", "10001", "00001", "00110", "00001", "10001", "01110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["01110", "10000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00001", "01110"],
}


def rng(seed: int, *stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream...) tuple."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def _templates() -> np.ndarray:
    """uint8[20, 21, 15]: digit d plain at 2d, dilated at 2d + 1."""
    out = []
    for d in range(10):
        g = np.array([[int(c) for c in row] for row in _GLYPHS[d]], np.uint8)
        g = np.kron(g, np.ones((3, 3), np.uint8))
        gp = np.pad(g, 1)
        out += [g, np.maximum(g, np.maximum(gp[2:, 1:-1], gp[1:-1, 2:]))]
    return np.stack(out)


def _keep_mask() -> np.ndarray:
    keep = np.ones((IMG, IMG), bool)
    for r in (slice(0, 2), slice(-2, None)):
        for c in (slice(0, 2), slice(-2, None)):
            keep[r, c] = False
    return keep.reshape(-1)


def _clean_digits(max_shift: int) -> np.ndarray:
    """uint8[20, S, S, 768]: every template at every (dy, dx) jitter, corners
    cropped, before noise (S = 2 * max_shift + 1)."""
    tmpl = _templates()
    h, w = tmpl.shape[1:]
    cy, cx = (IMG - h) // 2, (IMG - w) // 2
    s = 2 * max_shift + 1
    out = np.zeros((len(tmpl), s, s, IMG, IMG), np.uint8)
    for a in range(s):
        for b in range(s):
            dy = min(max(cy + a - max_shift, 0), IMG - h)
            dx = min(max(cx + b - max_shift, 0), IMG - w)
            out[:, a, b, dy:dy + h, dx:dx + w] = tmpl
    return out.reshape(len(tmpl), s, s, IMG * IMG)[..., _keep_mask()]


def bernoulli(g: np.random.Generator, shape, p: float) -> np.ndarray:
    """Bernoulli(p) bits from 16-bit uniforms: probability round(p * 2^16)
    / 2^16, within 2^-17 of p."""
    n = int(np.prod(shape))
    u = np.frombuffer(g.bytes(2 * n), np.uint16).reshape(shape)
    return u < np.uint16(round(p * 65536))


def digit_spikes(n: int, seed: int, *, flip_noise: float = 0.02,
                 max_shift: int = 2, chunk: int = 32768):
    """(spikes uint8[n, 768] in {0,1}, labels int32[n])."""
    g = rng(seed, 1)
    labels = g.integers(0, 10, size=n).astype(np.int32)
    t = 2 * labels + (g.random(n) < 0.5)
    a = g.integers(0, 2 * max_shift + 1, n)
    b = g.integers(0, 2 * max_shift + 1, n)
    table = _clean_digits(max_shift)
    out = np.empty((n, N_IN), np.uint8)
    for lo in range(0, n, chunk):
        hi = min(n, lo + chunk)
        out[lo:hi] = table[t[lo:hi], a[lo:hi], b[lo:hi]]
        out[lo:hi] ^= bernoulli(g, (hi - lo, N_IN), flip_noise)
    return out, labels


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """{0,1}[..., n] (n % 32 == 0) -> uint32[..., n/32] wire format: bit j
    of word k is element 32k + j."""
    assert bits.shape[-1] % LANE_BITS == 0, bits.shape
    by = np.packbits(bits.astype(np.uint8), axis=-1, bitorder="little")
    return np.ascontiguousarray(by).view("<u4")


def unpack_bits(words: np.ndarray, n: int = N_IN) -> np.ndarray:
    """Inverse of :func:`pack_bits`: uint32[..., n/32] -> uint8[..., n]."""
    by = np.ascontiguousarray(words).astype("<u4").view(np.uint8)
    return np.unpackbits(by, axis=-1, bitorder="little")[..., :n]


def rate_streams(frames: np.ndarray, t_of: np.ndarray, seed: int, *,
                 gain: float, chunk: int = 4096) -> dict[int, np.ndarray]:
    """Rate-coded event streams, packed: an active pixel fires with
    probability ``gain`` at every step (a 16-bit uniform below
    ``round(gain * 2^16)``, as in :func:`bernoulli`); a silent one never
    does, so uniforms are drawn for active pixels alone.

    ``frames`` {0,1}[n, 768], ``t_of`` int[n] per-stream length.  Returns
    ``{T: uint32[T, n_T, 24]}``; stream i is column ``pos[i]`` of
    ``out[t_of[i]]`` where ``pos`` ranks i among the streams of its T.
    """
    assert 0.0 <= gain <= 1.0, gain
    g = rng(seed, 2)
    thr = np.uint16(round(gain * 65536))
    out = {}
    on = frames.astype(bool)
    for t in sorted(set(int(x) for x in t_of)):
        sel = np.flatnonzero(t_of == t)
        packed = np.empty((t, sel.size, N_IN // LANE_BITS), np.uint32)
        for a in range(0, sel.size, chunk):
            b = min(sel.size, a + chunk)
            r, c = np.nonzero(on[sel[a:b]])
            u = np.frombuffer(g.bytes(2 * t * r.size), np.uint16)
            fire = np.zeros((t, b - a, N_IN), bool)
            fire[:, r, c] = u.reshape(t, r.size) < thr
            packed[:, a:b] = pack_bits(fire)
        out[t] = packed
    return out


def balanced_choice(choices, n: int, seed: int, stream: int) -> np.ndarray:
    """n draws from ``choices`` with every choice equally often (up to the
    remainder), in a seeded order: every seed gets the same multiset."""
    choices = np.asarray(choices)
    vals = np.resize(choices, n)
    return rng(seed, stream).permutation(vals)


def poisson_arrivals(rate_hz: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets (s) of a Poisson process over [0, seconds), conditioned
    on its count being rate * seconds: sorted uniform times.  The count is
    the same for every seed; only the arrival pattern changes."""
    n = int(round(rate_hz * seconds))
    return np.sort(rng(seed, 3).uniform(0.0, seconds, n))
