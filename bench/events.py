"""AER words and the seeded event generators the benchmark's traffic draws from.

Copied from the program so that no later change to it can move the yardstick:

* the word format and ``encode_sample`` from ``src/repro/core/aer.py``
  (type byte ``[31:24]``, address ``[23:12]``, tick ``[11:0]``);
* the Braille surrogate (``_sample_profile``) from ``src/repro/data/braille.py``;
* the cue-accumulation sample (``_make_sample``) from ``src/repro/data/cue.py``.

Everything here is NumPy and is driven by one ``numpy.random.Generator``,
so one seed gives one set of events.
"""

from __future__ import annotations

import dataclasses

import numpy as np

EVT_END = 0x01
EVT_LABEL = 0x02
EVT_SPIKE = 0x03
MAX_TICK = (1 << 12) - 1


def word(kind: int, addr, tick):
    return (np.uint32(kind) << np.uint32(24)) | (
        np.asarray(addr, np.uint32) << np.uint32(12)) | np.asarray(tick, np.uint32)


def spike_words(raster: np.ndarray) -> np.ndarray:
    """Tick-sorted spike words of one ``(T, N)`` raster (row-major order is
    tick order)."""
    t, n = np.nonzero(raster)
    return word(EVT_SPIKE, n, t)


def encode_sample(raster: np.ndarray, label: int, label_tick: int,
                  end_tick: int) -> np.ndarray:
    """One training sample as the SoC's BRAM image: spikes and the label word
    in tick order, then one END word."""
    words = np.concatenate([spike_words(raster),
                            np.array([word(EVT_LABEL, label, label_tick)])])
    order = np.argsort(words & MAX_TICK, kind="stable")
    return np.concatenate([words[order], np.array([word(EVT_END, 0, end_tick)])])


def pad_events(buffers, length: int | None = None) -> np.ndarray:
    length = length or max(len(b) for b in buffers)
    out = np.zeros((len(buffers), length), np.uint32)
    for i, b in enumerate(buffers):
        out[i, : len(b)] = b
    return out


def decode(words: np.ndarray, n_in: int, num_ticks: int):
    """Plain decode of padded ``(S, L)`` buffers: ``raster (T, S, n_in)``
    int8, ``label (S,)``, ``valid (T, S)`` for the readout window
    ``label_tick <= t <= end_tick`` (label delay 0)."""
    words = np.asarray(words, np.uint32)
    S = words.shape[0]
    kind = words >> 24
    addr = (words >> 12) & 0xFFF
    tick = words & MAX_TICK
    raster = np.zeros((num_ticks, S, n_in), np.int8)
    s_idx = np.broadcast_to(np.arange(S)[:, None], words.shape)
    sp = kind == EVT_SPIKE
    raster[tick[sp], s_idx[sp], addr[sp]] = 1
    label = np.where(kind == EVT_LABEL, addr, 0).max(axis=1)
    label_tick = np.where(kind == EVT_LABEL, tick, 0).max(axis=1)
    end_tick = np.where(kind == EVT_END, tick, 0).max(axis=1)
    t = np.arange(num_ticks)[:, None]
    valid = ((t >= label_tick[None]) & (t <= end_tick[None])).astype(np.int8)
    return raster, label.astype(np.int64), valid


# ------------------------------------------------------------------ Braille

DOTS = {
    "A": [(0, 0)],
    "E": [(0, 0), (1, 1)],
    "I": [(0, 1), (1, 0)],
    "O": [(0, 0), (0, 2), (1, 1)],
    "U": [(0, 0), (0, 2), (1, 2)],
    "Y": [(0, 0), (0, 2), (1, 0), (1, 2)],
    "Space": [],
}


@dataclasses.dataclass(frozen=True)
class BrailleParams:
    num_ticks: int = 256
    n_sensor_cols: int = 4
    n_sensor_rows: int = 3
    amplitude: float = 0.55
    sigma_t: float = 6.0
    sigma_row: float = 1.05
    p_noise: float = 0.045
    onset_jitter: float = 9.0
    speed_jitter: float = 0.12
    amp_jitter: float = 0.28
    space_texture: float = 0.35


def braille_profile(rng, letter: str, cfg: BrailleParams) -> np.ndarray:
    """Per-(tick, taxel) spike probabilities of one fingertip slide."""
    T = cfg.num_ticks
    p = np.full((T, cfg.n_sensor_rows, cfg.n_sensor_cols), cfg.p_noise)
    onset = T * 0.15 + rng.normal(0.0, cfg.onset_jitter)
    speed = (T * 0.55 / 2.0) * (1.0 + rng.normal(0.0, cfg.speed_jitter))
    amp = cfg.amplitude * (1.0 + rng.normal(0.0, cfg.amp_jitter))
    t = np.arange(T)[:, None, None]
    rows = np.arange(cfg.n_sensor_rows)[None, :, None]
    cols = np.arange(cfg.n_sensor_cols)[None, None, :]
    dots = list(DOTS[letter])
    weights = [1.0] * len(dots)
    if letter == "Space" and cfg.space_texture > 0:
        for _ in range(int(rng.integers(1, 3))):
            dots.append((int(rng.integers(0, 2)), int(rng.integers(0, 3))))
            weights.append(cfg.space_texture)
    for (dcol, drow), w in zip(dots, weights):
        t_pass = onset + (dcol + 0.35 * cols) * speed
        bump = np.exp(-0.5 * ((t - t_pass) / cfg.sigma_t) ** 2)
        align = np.exp(-0.5 * ((rows - drow) / cfg.sigma_row) ** 2)
        p = p + w * amp * bump * align
    return np.clip(p.reshape(T, -1), 0.0, 0.95)


def braille_characters(rng, letters, n: int, cfg: BrailleParams):
    """``n`` seeded slides, classes cycling over ``letters``: rasters
    ``(n, T, 12)`` bool and labels ``(n,)``."""
    labels = np.arange(n) % len(letters)
    rng.shuffle(labels)
    rasters = np.stack([
        rng.random((cfg.num_ticks, cfg.n_sensor_rows * cfg.n_sensor_cols))
        < braille_profile(rng, letters[c], cfg) for c in labels])
    return rasters, labels


# ---------------------------------------------------------------------- cue


@dataclasses.dataclass(frozen=True)
class CueParams:
    num_cues: int = 7
    cue_ticks: int = 10
    gap_ticks: int = 6
    delay_ticks: int = 10
    recall_ticks: int = 20
    p_active: float = 0.4
    p_noise: float = 0.05
    p_recall: float = 0.4
    group: int = 10

    @property
    def n_in(self) -> int:
        return 4 * self.group

    @property
    def num_ticks(self) -> int:
        return (self.num_cues * (self.cue_ticks + self.gap_ticks)
                + self.delay_ticks + self.recall_ticks)

    @property
    def recall_start(self) -> int:
        return self.num_cues * (self.cue_ticks + self.gap_ticks) + self.delay_ticks


def cue_sample(rng, cfg: CueParams):
    """One cue-accumulation sample: ``(raster, label, label_tick, end_tick)``."""
    T, G = cfg.num_ticks, cfg.group
    raster = np.zeros((T, cfg.n_in), np.float32)
    sides = rng.integers(0, 2, size=cfg.num_cues)
    label = int(sides.sum() * 2 > cfg.num_cues)
    for i, side in enumerate(sides):
        t0 = i * (cfg.cue_ticks + cfg.gap_ticks)
        block = rng.random((cfg.cue_ticks, G)) < cfg.p_active
        raster[t0: t0 + cfg.cue_ticks, side * G: (side + 1) * G] = block
    r0 = cfg.recall_start
    raster[r0: r0 + cfg.recall_ticks, 2 * G: 3 * G] = (
        rng.random((cfg.recall_ticks, G)) < cfg.p_recall)
    raster[:, 3 * G:] = rng.random((T, G)) < cfg.p_noise
    return raster, label, r0, T - 1
