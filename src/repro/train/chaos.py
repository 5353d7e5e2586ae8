"""Chaos harness: kill a training run at the worst moments, restart it, and
prove the recovered weights are *bitwise* what an uninterrupted run produces.

The harness has two halves:

* **worker** (``python -m repro.train.chaos --ckpt-dir ...``) — a real
  subprocess that builds a Braille END_B :class:`~repro.core.controller.
  OnlineLearner` with a checkpoint policy and runs ``fit(resume=True)``.
  Fault injection rides on the learner's ``on_commit`` hook:

  - ``--kill-at-commit K`` — ``SIGKILL`` itself at commit ``K`` (commit
    boundary: the checkpoint for ``K`` was just cut, possibly still
    in-flight on the async writer — a torn ``.tmp`` is part of the drill);
  - ``--kill-mid-save-step K`` — monkeypatch the checkpoint module's
    ``os.rename`` to ``SIGKILL`` the process the instant step ``K``'s
    atomic rename would commit — the canonical torn-save crash;
  - ``--sigterm-at-commit K`` — the *graceful* preemption drill: the
    installed handler finishes the batch, cuts a final blocking
    checkpoint, and the worker exits with :data:`STOPPED_RC`.

  A worker that reaches the configured epochs writes its final quantized
  weights (npz) + a result manifest (json) to ``--out`` and exits 0.

* **driver** (:func:`run_chaos`, used by ``tests/test_fault_tolerance.py``)
  — spawns the worker with a kill flag, watches it die, then respawns it
  *without* kill flags until it exits clean; :func:`golden_run` produces
  the uninterrupted reference weights in-process, :func:`golden_worker` in
  a worker on the drill's own platform.  Bitwise comparison is the
  caller's one-line job.

Determinism contract that makes the bitwise gate possible: batch order is
pure in ``(seed, epoch)`` (:mod:`repro.data.pipeline`), the stochastic-
rounding key chain is checkpointed, and (optionally) END_B accumulates on
the integer commit grid so even the 8→4 mesh-shrink restart is bit-exact
(``--deterministic`` / :data:`repro.core.quant.DW_COMMIT_SPEC`).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, Optional

import numpy as np

STOPPED_RC = 75        # worker stopped gracefully by SIGTERM (EX_TEMPFAIL)

_SRC = str(Path(__file__).resolve().parents[2])


def build_learner(
    ckpt_dir: Optional[str],
    *,
    backend: str = "scan",
    quantized: bool = True,
    epochs: int = 3,
    spb: int = 16,
    samples_per_class: int = 12,
    num_ticks: int = 48,
    seed: int = 3,
    mesh_devices: int = 0,
    deterministic: bool = False,
    checkpoint_every: int = 1,
    keep: int = 0,
    async_save: bool = True,
    registry=None,
):
    """A small Braille END_B learner + pipeline, identically parameterized
    for golden, interrupted and resumed runs (one construction point so the
    bitwise comparison can't be defeated by config drift)."""
    import jax

    from repro.core.backend import RuntimeConfig
    from repro.core.controller import ControllerConfig, OnlineLearner
    from repro.core.quant import DW_COMMIT_SPEC, WEIGHT_SPEC
    from repro.core.rsnn import Presets
    from repro.data.braille import BrailleConfig, make_braille_dataset
    from repro.data.pipeline import make_pipeline
    from repro.distributed.checkpoint import CheckpointPolicy
    from repro.launch.mesh import make_data_mesh
    from repro.optim.eprop_opt import EpropSGDConfig

    data = make_braille_dataset(
        "AEU", BrailleConfig(samples_per_class=samples_per_class,
                             num_ticks=num_ticks)
    )
    cfg = Presets.braille(n_classes=3, num_ticks=num_ticks,
                          quantized=quantized)
    ctrl = ControllerConfig(
        num_epochs=epochs, samples_per_batch=spb, commit="batch",
        shuffle=True, eval_every=10_000,
    )
    opt = (
        EpropSGDConfig(lr=0.01, clip=10.0, quant=WEIGHT_SPEC,
                       stochastic_round=True)
        if quantized
        else EpropSGDConfig(lr=0.01, clip=10.0)
    )
    mesh = make_data_mesh(mesh_devices) if mesh_devices > 1 else None
    rt = RuntimeConfig(
        backend=backend, mesh=mesh,
        commit_grid=DW_COMMIT_SPEC if deterministic else None,
    )
    policy = (
        CheckpointPolicy(directory=ckpt_dir, every=checkpoint_every,
                         keep=keep, async_save=async_save)
        if ckpt_dir is not None
        else None
    )
    learner = OnlineLearner(
        cfg, ctrl, opt, jax.random.key(seed + 100), runtime=rt,
        registry=registry, checkpoint=policy,
    )
    pipeline = make_pipeline(
        "arm", data, samples_per_batch=spb, shuffle_train=True, seed=seed
    )
    return learner, pipeline


def golden_run(**kw) -> Dict[str, np.ndarray]:
    """The uninterrupted reference: same learner, no checkpoints, no kills.
    Returns the final weights as host numpy."""
    learner, pipeline = build_learner(None, **kw)
    learner.fit(pipeline)
    return {k: np.asarray(v) for k, v in sorted(learner.weights.items())}


# ---------------------------------------------------------------- worker

def _arm_mid_save_kill(at_step: int) -> None:
    """SIGKILL this process the moment checkpoint ``at_step``'s atomic
    rename would land — the write is complete but never committed, leaving
    the torn ``.tmp`` the next manager must sweep."""
    from repro.distributed import checkpoint as ckpt_mod

    real_rename = ckpt_mod.os.rename
    tag = f"step_{at_step:09d}"

    def rename(src, dst):
        if tag == Path(str(dst)).name:
            os.kill(os.getpid(), signal.SIGKILL)
        return real_rename(src, dst)

    ckpt_mod.os.rename = rename


def run_worker(args: argparse.Namespace) -> int:
    t0 = time.time()
    learner, pipeline = build_learner(
        args.ckpt_dir,
        backend=args.backend,
        quantized=not args.float,
        epochs=args.epochs,
        spb=args.spb,
        samples_per_class=args.samples_per_class,
        num_ticks=args.ticks,
        seed=args.seed,
        mesh_devices=args.mesh_devices,
        deterministic=args.deterministic,
        checkpoint_every=args.every,
        async_save=not args.sync,
    )
    if args.kill_mid_save_step is not None:
        _arm_mid_save_kill(args.kill_mid_save_step)
    learner.install_signal_handlers()

    resumed_from = learner._commits if learner.restore_checkpoint() else None
    first_commit_s: Dict[str, float] = {}

    def on_commit(lrn, commits):
        first_commit_s.setdefault("t", time.time() - t0)
        if args.kill_at_commit is not None and commits >= args.kill_at_commit:
            os.kill(os.getpid(), signal.SIGKILL)
        if (
            args.sigterm_at_commit is not None
            and commits >= args.sigterm_at_commit
        ):
            os.kill(os.getpid(), signal.SIGTERM)

    learner.fit(pipeline, on_commit=on_commit)
    learner.restore_signal_handlers()
    if learner.stopped_by_signal:
        return STOPPED_RC

    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        np.savez(
            out.with_suffix(".npz"),
            **{k: np.asarray(v) for k, v in sorted(learner.weights.items())},
        )
        train_acc = learner.log.train_acc[-1] if learner.log.train_acc else None
        out.with_suffix(".json").write_text(json.dumps({
            "commits": int(learner._commits),
            "resumed_from": resumed_from,
            "recovery_s": first_commit_s.get("t"),
            "wall_s": time.time() - t0,
            "train_acc": train_acc,
        }))
    return 0


# ---------------------------------------------------------------- driver

def spawn(
    argv,
    mesh_devices: int = 0,
    timeout: float = 600.0,
) -> subprocess.CompletedProcess:
    """Run one worker subprocess with a pinned JAX environment (CPU platform,
    explicit virtual device count — subprocess determinism must not depend
    on whatever XLA_FLAGS the parent happened to inherit)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env["JAX_PLATFORMS"] = "cpu"
    if mesh_devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={mesh_devices}"
        )
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "repro.train.chaos", *map(str, argv)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def run_chaos(
    ckpt_dir: str,
    out: str,
    kill_args,
    worker_args,
    mesh_devices: int = 0,
    restart_mesh_devices: Optional[int] = None,
    max_restarts: int = 5,
) -> Dict:
    """The full drill: one doomed worker, then restarts until a clean exit.

    ``kill_args`` ride only on the first spawn; restarts run the identical
    worker without them.  ``restart_mesh_devices`` re-hosts the restarts on
    a different virtual device count (the elastic 8→4 shrink drill).
    Returns the worker's result manifest plus the restart count.
    """
    base = ["--ckpt-dir", ckpt_dir, "--out", out, *map(str, worker_args)]
    first = spawn(base + list(map(str, kill_args)), mesh_devices=mesh_devices)
    if first.returncode == 0:
        raise RuntimeError(
            f"doomed worker exited clean — kill never fired\n{first.stdout}"
            f"\n{first.stderr}"
        )
    restarts = 0
    rc_mesh = mesh_devices if restart_mesh_devices is None else restart_mesh_devices
    while restarts < max_restarts:
        restarts += 1
        proc = spawn(base, mesh_devices=rc_mesh)
        if proc.returncode == 0:
            break
        if proc.returncode not in (-signal.SIGKILL, STOPPED_RC):
            raise RuntimeError(
                f"restart {restarts} died unexpectedly rc={proc.returncode}"
                f"\n{proc.stdout}\n{proc.stderr}"
            )
    else:
        raise RuntimeError(f"no clean exit after {max_restarts} restarts")
    result = json.loads(Path(out).with_suffix(".json").read_text())
    result["restarts"] = restarts
    return result


def golden_worker(
    ckpt_dir: str, out: str, worker_args, mesh_devices: int = 0
) -> Dict[str, np.ndarray]:
    """The uninterrupted reference run in a worker subprocess — the same
    pinned platform as the drill's workers, so both sides of the bitwise
    comparison run the same arithmetic and the caller's process never
    touches JAX for it.  Returns the final weights as host numpy."""
    base = ["--ckpt-dir", ckpt_dir, "--out", out, *map(str, worker_args)]
    proc = spawn(base, mesh_devices=mesh_devices)
    if proc.returncode != 0:
        raise RuntimeError(
            f"golden worker failed rc={proc.returncode}\n{proc.stdout}"
            f"\n{proc.stderr}"
        )
    return load_result_weights(out)


def load_result_weights(out: str) -> Dict[str, np.ndarray]:
    with np.load(Path(out).with_suffix(".npz")) as z:
        return {k: z[k] for k in z.files}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--backend", default="scan")
    ap.add_argument("--float", action="store_true",
                    help="float weights (default: quantized chip mode)")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--spb", type=int, default=16)
    ap.add_argument("--samples-per-class", type=int, default=12)
    ap.add_argument("--ticks", type=int, default=48)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--mesh-devices", type=int, default=0)
    ap.add_argument("--deterministic", action="store_true",
                    help="END_B on the integer commit grid (mesh-invariant)")
    ap.add_argument("--every", type=int, default=1,
                    help="checkpoint every N commits")
    ap.add_argument("--sync", action="store_true",
                    help="blocking saves (default: async)")
    ap.add_argument("--kill-at-commit", type=int, default=None)
    ap.add_argument("--kill-mid-save-step", type=int, default=None)
    ap.add_argument("--sigterm-at-commit", type=int, default=None)
    return run_worker(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
