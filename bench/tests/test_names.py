"""Configurations, traffic mixes, drivers and metric readers are found by
the names in BENCHMARK.json."""

import dataclasses

from bench import harness
from bench.model import rsnn_config


def test_every_cell_and_metric_resolves_by_name():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        _, config, tr = harness.resolve(spec, cell["name"])
        assert config["name"] == cell["config"]
        assert callable(harness.driver(tr["driver"]).run)
        got = {m["name"] for m in harness.metrics_for(spec, cell["name"], True)}
        assert got, cell["name"]
        e2e = {m["name"] for m in harness.metrics_for(spec, cell["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_configuration_files_build_the_programs_published_configs():
    from repro.configs import reckon_braille, reckon_cue

    from bench.model import optimizer_config

    spec = harness.load_spec()
    for name, prog in (("braille_q.train", reckon_braille),
                       ("cue_q.train", reckon_cue)):
        _, config, _ = harness.resolve(spec, name)
        built = rsnn_config(config, prog.CONFIG_QUANT.num_ticks)
        assert built == dataclasses.replace(prog.CONFIG_QUANT)
        assert optimizer_config(config) == prog.QUANT_OPT


def test_unknown_device_kind_is_an_error():
    import pytest

    with pytest.raises(harness.NoChip):
        harness.peaks_for("cpu")
