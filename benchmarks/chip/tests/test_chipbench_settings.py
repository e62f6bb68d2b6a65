"""The harness refuses a trainer that runs what the reference does not
model: another scheme, wire, topology, penalty or optimizer setting."""
import types

import pytest

from chipbench import harness, manifest, reference

CELL = manifest.cell(manifest.load(), "qwen3-4b.ring4.h2")


def trainer(**change):
    """A stand-in with the settings of the ring cell as stated."""
    opt, mix = CELL["model"]["optimizer"], CELL["traffic_mix"]
    s = dict(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
             weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"],
             factored=False, prox_step=mix["prox_step"],
             local_steps=mix["local_steps"], scheme=mix["scheme"],
             eta0=mix["eta0"], budget_init=1.0, codec_name="native",
             offsets=[1, 3], dynamic=False, async_cfg=None)
    s.update(change)
    shapes = reference.param_shapes(reference.arch(CELL["model"]))
    ns = types.SimpleNamespace
    return ns(
        acfg=ns(**{k: s[k] for k in ("lr", "b1", "b2", "eps",
                                     "weight_decay", "grad_clip",
                                     "factored")}),
        ccfg=ns(prox_step=s["prox_step"], local_steps=s["local_steps"],
                penalty=ns(scheme=s["scheme"], eta0=s["eta0"],
                           budget_init=s["budget_init"])),
        num_nodes=mix["nodes"], codec_name=s["codec_name"],
        offsets=s["offsets"], dynamic=s["dynamic"],
        async_cfg=s["async_cfg"],
        model=ns(abstract_params=lambda: shapes))


RUN = types.SimpleNamespace(model=CELL["model"], mix=CELL["traffic_mix"],
                            nodes=CELL["traffic_mix"]["nodes"])


def test_the_stated_settings_pass():
    harness.check_settings(RUN, trainer())


@pytest.mark.parametrize("change", [
    {"codec_name": "int8"}, {"scheme": "ap"}, {"offsets": [1, 2, 3]},
    {"dynamic": True}, {"async_cfg": "on"}, {"eta0": 10.0},
    {"local_steps": 4}, {"budget_init": 0.0}, {"lr": 1e-2}],
    ids=lambda c: ",".join(f"{k}={v}" for k, v in c.items()))
def test_unmodelled_settings_are_refused(change):
    with pytest.raises(RuntimeError, match="does not run the configuration"):
        harness.check_settings(RUN, trainer(**change))
