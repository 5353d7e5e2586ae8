"""The LSNN of e-prop's evidence-accumulation task (Bellec et al., "A
solution to the learning dilemma for recurrent networks of spiking neurons",
Nat. Comm. 11:3625, 2020): 40 inputs, one recurrent layer of 50 LIF and 50
adaptive-threshold (ALIF) neurons, 2 softmax outputs, error only during the
recall cue.

The trial is the cue task of :mod:`repro.data.cue` at 1 ms ticks: 7 cues of
100 ticks with gaps of 50, a 1,050-tick delay and a 150-tick recall, so the
memory has to span about a second (T = 2,250).  Cue and recall channels fire
at 40 Hz, noise channels at 10 Hz.

Float mode: ReckOn's fixed-point datapath has no adaptive threshold.  The
optimizer is the repo's e-prop SGD (the paper trains with Adam).
"""

from repro.core.rsnn import Presets
from repro.data.cue import CueConfig
from repro.optim.eprop_opt import EpropSGDConfig

CONFIG = Presets.lsnn_evidence()

TASK = CueConfig(cue_ticks=100, gap_ticks=50, delay_ticks=1050,
                 recall_ticks=150, p_active=0.04, p_noise=0.01,
                 p_recall=0.04)

OPT = EpropSGDConfig(lr=1e-2, clip=10.0)

assert TASK.num_ticks == CONFIG.num_ticks
