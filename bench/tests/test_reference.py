"""A configuration names its plain reference and its replicas: the
loader, and the configurations the harness refuses."""

import dataclasses
import json
import os

import pytest

from bench import model, program, run

CONFIGS = ("vgg19-8-dcn2", "segnet-8-dcn2", "vgg19-8-dcn2-dp4")


def config_of(name: str) -> dict:
    with open(os.path.join(run.ROOT, "bench", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_loader_gives_what_bench_model_gives(name):
    config = config_of(name)
    assert config["reference"] == "bench/model.py"
    ref = run.reference_module(run.ROOT, config)
    assert ref is run.reference_module(run.ROOT, config)
    net = ref.Net.from_config(config)
    direct = model.Net.from_config(config)
    assert dataclasses.asdict(net) == dataclasses.asdict(direct)
    assert ref.layers(net) == model.layers(direct)
    assert ref.forward_flops(net) == model.forward_flops(direct)


@pytest.mark.parametrize("name", CONFIGS)
def test_program_config_takes_every_model_key(name):
    config = config_of(name)
    got = program.program_config(config["model"])
    m = config["model"]
    assert got.name == m["arch"]
    assert {k: getattr(got, k) for k in m if k != "arch"} == \
        {k: v for k, v in m.items() if k != "arch"}
    # A reference's Net reaches the same program configuration.
    assert program.program_config(model.Net.from_config(config)) == got


def test_missing_reference_is_refused():
    config = dict(config_of("vgg19-8-dcn2"), reference="bench/models/no.py")
    with pytest.raises(SystemExit, match="'bench/models/no.py', which is "
                                         "not a file of the checkout"):
        run.check_config(run.ROOT, config)
    config.pop("reference")
    with pytest.raises(SystemExit, match="names no reference"):
        run.check_config(run.ROOT, config)


def test_graph_key_other_than_the_replica_count_is_refused():
    config = dict(config_of("vgg19-8-dcn2"),
                  graph={"data_parallel": 4, "tile": 16})
    with pytest.raises(SystemExit, match=r"graph keys \['tile'\] are not "
                                         r"the deployment's to state"):
        run.check_config(run.ROOT, config)
    run.check_config(run.ROOT, config_of("vgg19-8-dcn2-dp4"))


def test_every_cell_loads():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for name in cells:
        found = run.load_cell(run.ROOT, name)
        assert found["config"]["name"] == found["cell"]["config"]
