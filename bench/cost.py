"""The yardstick: operations and bytes the algorithm needs, from shapes alone.

Counts depend only on the network's widths ``(n_in, n_hid, n_out)`` and the
work done (session-ticks, or samples of ``T`` ticks), never on how a kernel
tiles, pads lanes or streams (``blocked`` / ``dma``), so a kernel change
cannot make them stale.  They replace the byte model of
``src/repro/kernels/traffic.py`` and ``benchmarks/roofline.py``, which count
what the current tiling executes.

Operations (one MAC = 2 operations), per sample-tick, from the paper's dense
datapath (one ReckOn tick):

* forward: ``x @ W_in`` + ``z @ W_rec`` + ``z @ W_out``
  = ``n_in*H + H*H + H*O`` MACs (Braille 12-38-3: 2,014; cue 40-100-2: 14,200);
* e-prop training adds, per tick, the learning signal ``L = err @ B^T``
  (``H*O``) and the three factored weight-gradient contractions
  ``xbar^T G``, ``pbar^T G``, ``zbar^T err`` (``n_in*H + H*H + H*O``):
  ``2*(n_in*H + H*H + H*O) + H*O`` MACs (Braille 4,142; cue 28,600).

Element-wise work (leaks, thresholds, trace filters) is not counted, so these
are lower bounds on operations and a share of a peak computed from them
cannot pass 100%.

Bytes, the least an implementation must move through HBM:

* streaming sessions: each live session's carry ``(v, z, y, acc_y, n_spk)``
  read and written once per tile, f32 (``2 * 4 * (2H + 2O + 1)``); each
  input event once as a 4-byte AER word; the 8-bit weight image once per
  tile;
* training: each sample's input at one bit per (tick, channel), the 8-bit
  weight image once and the f32 ``dw`` once per commit.
"""

from __future__ import annotations


def forward_macs(n_in: int, n_hid: int, n_out: int) -> int:
    return n_in * n_hid + n_hid * n_hid + n_hid * n_out


def train_macs(n_in: int, n_hid: int, n_out: int) -> int:
    return 2 * forward_macs(n_in, n_hid, n_out) + n_hid * n_out


def weight_bytes(n_in: int, n_hid: int, n_out: int) -> int:
    return forward_macs(n_in, n_hid, n_out)          # one byte per 8-bit code


def session_ops(dims, session_ticks: int) -> float:
    return 2.0 * forward_macs(*dims) * session_ticks


def session_bytes(dims, lanes: int, events: int, tiles: int) -> float:
    n_in, n_hid, n_out = dims
    carry = 4 * (2 * n_hid + 2 * n_out + 1)
    return 2.0 * carry * lanes + 4.0 * events + weight_bytes(*dims) * tiles


def train_ops(dims, samples: int, num_ticks: int) -> float:
    return 2.0 * train_macs(*dims) * samples * num_ticks


def train_bytes(dims, samples: int, num_ticks: int, commits: int) -> float:
    n_in = dims[0]
    per_commit = weight_bytes(*dims) + 4 * forward_macs(*dims)
    return samples * num_ticks * n_in / 8.0 + per_commit * commits


def least_seconds(ops: float, nbytes: float, peak_ops: float,
                  peak_bw: float) -> float:
    """The roofline: the least time the chip could take for this work."""
    return max(ops / peak_ops, nbytes / peak_bw)
