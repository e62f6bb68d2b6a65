"""BENCHMARK.json: every name legal, every cell's files found by name."""
import json

import pytest

from chipbench import manifest, reference
from chipbench.trace import load_reader

BENCH = manifest.load()


def test_names_and_units_are_legal():
    manifest.check_names(BENCH)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(workload):
    cell = manifest.cell(BENCH, workload)
    assert cell["traffic_mix"]["nodes"] == cell["chips"]
    assert {"loss_rel", "grad_gap", "update_gap"} <= set(cell["limits"])
    assert cell["end_to_end"] and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(load_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in cell["end_to_end"]}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(conf):
    model = json.loads((manifest.ROOT / conf["file"]).read_text())
    assert model["source"] == conf["source"]
    assert sorted(model["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert key in model and key in model["published"]
        assert model[key] != model["published"][key]
    a = reference.arch(model)
    assert reference.param_count(a) == model["assumed"]["param_count"]


def test_unknown_names_are_refused():
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"][0]["name"] = "has space"
    with pytest.raises(manifest.ManifestError):
        manifest.check_names(bad)
    with pytest.raises(manifest.ManifestError):
        manifest.cell(BENCH, "no-such-cell")


@pytest.mark.parametrize("nodes,drop,add", [
    (1, None, "eta0"), (4, "wire_codec", None), (4, "prox_step", None)])
def test_round_settings_stated_where_nodes_exchange(tmp_path, nodes, drop,
                                                    add):
    """A ring's traffic states every round setting; one node's states none."""
    ring = manifest.cell(BENCH, "qwen3-4b.ring4.h2")["traffic_mix"]
    mix = dict(ring, nodes=nodes)
    if nodes == 1:
        mix = {k: v for k, v in mix.items()
               if k not in manifest.ROUND_SETTINGS}
        mix[add] = ring[add]
    else:
        mix.pop(drop)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "probe.cell", "config": "qwen3-4b",
                               "traffic": "probe", "chips": nodes,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    here = manifest.HERE
    try:
        manifest.HERE = tmp_path
        (tmp_path / "traffic").mkdir()
        (tmp_path / "limits").mkdir()
        (tmp_path / "traffic" / "probe.json").write_text(json.dumps(mix))
        (tmp_path / "limits" / "probe.cell.json").write_text("{}")
        conf = tmp_path / BENCH["configs"][0]["file"]
        conf.parent.mkdir(parents=True)
        conf.write_text((manifest.ROOT / BENCH["configs"][0]["file"])
                        .read_text())
        with pytest.raises(manifest.ManifestError):
            manifest.cell(bench, "probe.cell", root=tmp_path)
    finally:
        manifest.HERE = here
