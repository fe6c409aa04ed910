"""A run whose timed path is broken underneath reads ``correct`` false:
once for each fault the cells can have.  (The cells run on one chip, so
there is no exchange between chips to leave out.)"""

import pytest

from bench.tests.conftest import run_smoke


def test_a_token_altered_where_it_is_produced(smoke_root, monkeypatch):
    from repro.serve import engine as E
    real = E.make_paged_decode_tick

    def altered(cfg, max_len, sampler, page_size):
        tick = real(cfg, max_len, sampler, page_size)

        def bad(*args):
            emitted, *rest = tick(*args)
            return ((emitted + 1) % cfg.vocab_size, *rest)
        return bad

    monkeypatch.setattr(E, "make_paged_decode_tick", altered)
    for cell in ("granite-chat-prefix", "qwen15-decode-backlog"):
        res = run_smoke(smoke_root, cell)
        assert not res["correct"], res["checks"]
        assert res["checks"]["max_logit_gap"]["value"] > \
            res["checks"]["max_logit_gap"]["limit"]


def _patch_step(monkeypatch, fault):
    from repro.launch import steps as S
    real = S.make_phase2_step

    def broken(cfg, opt, **kw):
        step = real(cfg, opt, **kw)

        def bad(student, teacher, buffer, opt_state, batch, i):
            return fault(step, student, teacher, buffer, opt_state, batch, i)
        return bad

    monkeypatch.setattr(S, "make_phase2_step", broken)


def unchanged(step, student, teacher, buffer, opt_state, batch, i):
    _, _, metrics = step(student, teacher, buffer, opt_state, batch, i)
    return student, opt_state, metrics


def half_batch(step, student, teacher, buffer, opt_state, batch, i):
    half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
    return step(student, teacher, buffer, opt_state, half, i)


@pytest.mark.parametrize("fault, number", [(unchanged, "delta_gap"),
                                           (half_batch, "grad_gap")])
def test_a_broken_training_step(smoke_root, monkeypatch, fault, number):
    _patch_step(monkeypatch, fault)
    res = run_smoke(smoke_root, "granite-p2-clone")
    assert not res["correct"], res["checks"]
    c = res["checks"][number]
    assert c["value"] > c["limit"], res["checks"]
