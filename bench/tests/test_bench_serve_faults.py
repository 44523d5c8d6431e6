"""The serve cell's comparison, driven through the rest of a run at a size
the CPU holds (the harness's look for a chip skipped): a sound run is
correct; the control (the reference in float8 in the program's place) and
each fault the cell can have is not.  The cell runs on one chip, so it has
no exchange between chips to leave out, and no batch mean to take over
half of the rows."""
import pytest

from bench.drivers import serve_open_loop as drv
from bench.tests.tiny import CpuHarness, tiny_cell


def _run(seed=2 ** 31 + 9):
    # std 0.5 spreads the logits at these widths so that the float8
    # control's gap (1.7 to 1.8 here) lies as far above the cell's limit as
    # it does at full size on the chip (1.4 to 2.3); a sound run reads 0.05
    cell = tiny_cell("qwen3-4b.serve.chat", init=0.5)
    res = drv.run(cell, seed, 1.0, CpuHarness())
    assert res["check"]["served_tokens"] > 20
    return res


def test_sound_run_is_correct():
    res = _run()
    assert res["check"]["correct"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["ttft_p90_ms"] > 0 and m["itl_mean_ms"] > 0
    assert m["serve_tokens_per_s"] > 0


def test_control_in_the_programs_place_is_not_correct(monkeypatch):
    real = drv.check_served

    def control(*args, **kw):
        return real(*args, control=True, **kw)

    monkeypatch.setattr(drv, "check_served", control)
    assert not _run()["check"]["correct"]


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve import sampling
    real = sampling.make_sampler

    def altered(*a, **kw):
        f = real(*a, **kw)
        return lambda logits, key: (f(logits, key) + 1) % logits.shape[-1]

    monkeypatch.setattr(sampling, "make_sampler", altered)
    assert not _run()["check"]["correct"]


def test_step_returning_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.serve import kvcache
    monkeypatch.setattr(kvcache, "scatter_step",
                        lambda pool, updates, phys: pool)
    assert not _run()["check"]["correct"]


@pytest.mark.parametrize("change", [dict(qk_norm=False),
                                    dict(act="gelu_mlp")])
def test_program_without_a_stated_part_is_not_correct(monkeypatch, change):
    # the program built without qk-norm, or with a plain MLP in place of
    # the gated one: the reference keeps what the configuration states
    import dataclasses
    from bench import spec
    real = spec.model_config
    monkeypatch.setattr(spec, "model_config", lambda *a: dataclasses.replace(
        real(*a), **change))
    res = _run()
    assert not res["check"]["correct"]
    assert res["check"]["numbers"]["param_leaves_off"]["value"] > 0
