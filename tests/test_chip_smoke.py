"""chip_smoke.py at smoke size on the CPU.

The script's phases are functions of the configuration and the mesh; here
they run on the CPU host with Pallas in interpret mode, so a broken check
or a wrong phase wiring shows up before anything is sent to a chip.  The
script itself must refuse to report a result without a TPU.
"""

import dataclasses
import importlib.util
import os

import jax
import pytest

from repro.configs import registry
from repro.launch.mesh import make_device_mesh

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "[device] platform=cpu" in out
    for line in out.splitlines():
        assert not line.startswith("{"), "a result line without a TPU"


def test_kernel_checks_pass_in_interpret_mode(smoke):
    cfg = registry.get_smoke_config("granite-3-2b")
    smoke.check_paged_decode(cfg, slots=2, max_len=64, page_size=8)
    smoke.check_kd_loss(rows=32, vocab=cfg.padded_vocab, valid=cfg.vocab_size)


def test_serve_phase_checks_at_smoke_size(smoke):
    from repro.launch import serve
    finished, engine = serve.main([
        "--arch", "granite-3-2b", "--paged", "--stream", "poisson",
        "--slots", "2", "--requests", "6", "--max-len", "96",
        "--page-size", "8", "--prompt-max", "24", "--out-max", "4",
        "--shared-prefix", "16"])
    smoke.check_served(finished, engine, 6, engine.cfg.padded_vocab)
    assert "ENTRY" in smoke.decode_tick_text(engine)


def test_phase2_loss_finite_and_falls_at_smoke_size(smoke):
    cfg = dataclasses.replace(registry.get_smoke_config("granite-3-2b"),
                              dtype="bfloat16", param_dtype="bfloat16")
    mesh = make_device_mesh(jax.devices()[:1])
    losses, mnorms, _, text = smoke.run_phase2(cfg, mesh, 3, 2, 32, "test")
    smoke.check_falls(losses, "test")
    assert len(losses) == len(mnorms) == 3 and "ENTRY" in text


@pytest.mark.parametrize("scale,agree", [(1 + 2e-4, True), (0.85, False)])
def test_shard_check_catches_a_gradient_of_the_wrong_scale(smoke, scale,
                                                           agree):
    """Losses that agree do not hide a moment norm off by a dropped row
    shard's share; reordering noise passes."""
    one = ([11.96, 11.98], [0.97, 1.17])
    four = (one[0], [m * scale for m in one[1]])
    if agree:
        smoke.check_shards_agree(one, four)
    else:
        with pytest.raises(SystemExit):
            smoke.check_shards_agree(one, four)
