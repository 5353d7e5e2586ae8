"""Accuracy parity between the commit modes and the two datapaths.

END_B (one summed commit per BRAM-sized batch) must learn about as well as
END_S (one commit per sample), and the bit-true quantized datapath about as
well as float, at the smoke budgets of the paper's two tasks: Braille AEU,
12 epochs, batches of 70, seed 1; cue accumulation, 10 epochs on the
paper's 50/50 splits, batches of 10, the mean over seeds 0-2.
"""

import functools

import jax
import numpy as np
import pytest

from repro.configs import reckon_cue
from repro.core.backend import RuntimeConfig
from repro.core.controller import ControllerConfig, OnlineLearner
from repro.core.quant import WEIGHT_SPEC
from repro.core.rsnn import Presets
from repro.data.braille import make_braille_dataset
from repro.data.cue import CueConfig, make_cue_dataset
from repro.data.pipeline import make_pipeline
from repro.optim.eprop_opt import EpropSGDConfig

SCAN = RuntimeConfig(backend="scan")


@pytest.fixture(scope="module")
def braille_test_acc():
    """Test accuracy after the 12-epoch Braille AEU run, by commit mode and
    datapath; each run once per module, as two tests share the float END_S
    baseline."""
    return functools.cache(_braille_test_acc)


def _braille_test_acc(commit: str, quantized: bool) -> float:
    """Batch commits take twice the float lr; the quantized datapath keeps
    lr 0.01 and commits to the 8-bit SRAM grid with stochastic rounding."""
    epochs = 12
    data = make_braille_dataset("AEU")
    cfg = Presets.braille(n_classes=3, num_ticks=data["train"]["num_ticks"],
                          quantized=quantized)
    n_train = data["train"]["events"].shape[0]
    opt = EpropSGDConfig(
        lr=0.01 if (commit == "sample" or quantized) else 0.02,
        clip=10.0, decay_tau=25.0 * n_train,
        quant=WEIGHT_SPEC if quantized else None, stochastic_round=quantized,
    )
    learner = OnlineLearner(
        cfg, ControllerConfig(num_epochs=epochs, eval_every=epochs,
                              commit=commit),
        opt, jax.random.key(1), runtime=SCAN,
    )
    pipe = make_pipeline("arm", data, samples_per_batch=70)
    for ep in range(epochs):
        learner.train_epoch(pipe, ep)
    return learner.eval_epoch(pipe, 0, split="test")


def test_braille_batch_commit_within_010_of_sequential(braille_test_acc):
    end_s = braille_test_acc("sample", False)
    end_b = braille_test_acc("batch", False)
    assert end_s - end_b <= 0.10, (end_s, end_b)


def test_quantized_braille_within_2_points_of_float(braille_test_acc):
    """The paper's software-to-chip margin: quantized END_S loses at most 2
    points to float END_S, and quantized END_B is within 0.10 of it."""
    float_s = braille_test_acc("sample", False)
    quant_s = braille_test_acc("sample", True)
    quant_b = braille_test_acc("batch", True)
    assert float_s - quant_s <= 0.02 and abs(quant_s - quant_b) <= 0.10, (
        float_s, quant_s, quant_b)


def test_cue_batch_commit_within_010_of_sequential():
    """A single 50-sample run is a high-variance estimate, so the gate
    compares the 3-seed means of the per-epoch validation accuracy."""
    ccfg = CueConfig()
    data = make_cue_dataset(50, 50, cfg=ccfg)
    cfg = reckon_cue.config_for(quantized=False, num_ticks=ccfg.num_ticks)
    means = {}
    for commit, mode in (("sample", "xheep"), ("batch", "arm")):
        accs = []
        for seed in (0, 1, 2):
            learner = OnlineLearner(
                cfg, ControllerConfig(num_epochs=10, samples_per_epoch=50,
                                      commit=commit),
                EpropSGDConfig(lr=0.01, clip=10.0), jax.random.key(seed),
                runtime=SCAN,
            )
            log = learner.fit(make_pipeline(mode, data, samples_per_batch=10))
            accs.append(np.mean(log.val_acc))
        means[commit] = float(np.mean(accs))
    assert abs(means["sample"] - means["batch"]) <= 0.10, means
