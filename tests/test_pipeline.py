"""The training pipelines' device decode: one compiled program per batch
shape, bitwise equal to the eager decode and to the serving path's NumPy
mirror."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aer
from repro.core.controller import decode_events_to_batch
from repro.data.pipeline import BatchedOffloadPipeline, ResidentPipeline
from repro.serve.batching import decode_events_host


def _buffers(rng, n, n_in, num_ticks, density=0.02, dup=0.1):
    """``n`` AER buffers of different lengths, some spike words repeated
    (duplicate spikes decode to one)."""
    bufs = []
    for i in range(n):
        raster = (rng.random((num_ticks, n_in)) < density * (1 + i % 3))
        end = int(rng.integers(num_ticks // 2, num_ticks))
        raster[end + 1:] = False
        words = aer.encode_sample(raster.astype(np.float32), i % 3,
                                  int(rng.integers(0, end + 1)), end)
        spikes = words[(words >> 24) == aer.EVT_SPIKE]
        extra = spikes[rng.random(spikes.size) < dup]
        bufs.append(np.concatenate([extra, words]))
    return bufs


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@pytest.mark.parametrize("n_in,num_ticks,label_delay,width", [
    (12, 256, 0, 576),    # Braille widths, fixed pad
    (12, 256, 7, None),   # Braille widths, padded to the longest buffer
    (40, 150, 0, 640),    # cue widths, fixed pad
    (40, 150, 18, 640),   # cue widths, delayed supervision
])
def test_jitted_decode_bitwise_equals_eager_and_host(n_in, num_ticks,
                                                     label_delay, width):
    rng = np.random.default_rng(n_in * 1000 + num_ticks + label_delay)
    bufs = _buffers(rng, 9, n_in, num_ticks)
    words = aer.pad_events(bufs, width)
    assert len({len(b) for b in bufs}) > 1 and (words == 0).any()

    got = decode_events_to_batch(jnp.asarray(words), n_in, num_ticks,
                                 label_delay)

    # the eager decode, one sample at a time
    for i, w in enumerate(words):
        s = aer.decode_sample(jnp.asarray(w), n_in, num_ticks)
        mask = aer.supervision_mask(s.label_tick, s.end_tick, num_ticks,
                                    label_delay)
        np.testing.assert_array_equal(_bits(got["raster"][i]),
                                      _bits(s.raster))
        np.testing.assert_array_equal(_bits(got["valid"][i]), _bits(mask))
        assert int(got["label"][i]) == int(s.label)

    # the same body run op by op
    with jax.disable_jit():
        eager = decode_events_to_batch(jnp.asarray(words), n_in, num_ticks,
                                       label_delay)
    for k in ("raster", "valid", "label"):
        assert got[k].dtype == eager[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(eager[k]))

    # the serving path's host decode of the unpadded buffers
    raster_h, valid_h, labels_h = decode_events_host(bufs, n_in, num_ticks,
                                                     label_delay)
    np.testing.assert_array_equal(_bits(got["raster"]),
                                  _bits(np.moveaxis(raster_h, 1, 0)))
    np.testing.assert_array_equal(_bits(got["valid"]), _bits(valid_h.T))
    np.testing.assert_array_equal(np.asarray(got["label"]), labels_h)


def _split(rng, n, width, n_in=12, num_ticks=32):
    return {"events": aer.pad_events(_buffers(rng, n, n_in, num_ticks), width),
            "n_in": n_in, "num_ticks": num_ticks}


@pytest.mark.parametrize("n,spb,programs", [(30, 10, 1), (25, 10, 2)])
def test_offload_pipeline_builds_one_decode_per_shape(n, spb, programs):
    """Two epochs decode every batch; full batches share one program and a
    ragged last chunk builds exactly one more."""
    rng = np.random.default_rng(n)
    split = _split(rng, n, width=64 + n)   # a shape no other test decodes
    pipe = BatchedOffloadPipeline({"train": split}, samples_per_batch=spb,
                                  label_delay=3)
    full = decode_events_to_batch(jnp.asarray(split["events"]), 12, 32, 3)
    built = decode_events_to_batch._cache_size()
    batches = 0
    for epoch in range(2):
        for i, b in enumerate(pipe.batches("train", epoch)):
            rows = slice(i * spb, (i + 1) * spb)
            for k in ("raster", "valid", "label"):
                np.testing.assert_array_equal(np.asarray(b[k]),
                                              np.asarray(full[k][rows]))
            batches += 1
    assert batches == 2 * -(-n // spb)
    assert pipe.stats.decodes == batches
    assert pipe.stats.decode_programs == programs
    assert decode_events_to_batch._cache_size() - built == programs


def test_resident_pipeline_builds_one_decode_per_split():
    rng = np.random.default_rng(5)
    data = {"train": _split(rng, 20, 61), "test": _split(rng, 8, 61)}
    built = decode_events_to_batch._cache_size()
    pipe = ResidentPipeline(data)
    assert pipe.stats.decodes == 2
    assert pipe.stats.decode_programs == 2
    assert decode_events_to_batch._cache_size() - built == 2
    for split in data:
        (batch,) = pipe.batches(split, 0)
        assert batch["raster"].shape == (data[split]["events"].shape[0], 32, 12)
