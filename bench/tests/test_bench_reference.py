"""The float32 references against the program at small sizes on the CPU:
the qwen3 block (GQA, q/k RMSNorm, RoPE, SwiGLU, untied head) and the paper
transformer's block (LayerNorm, tanh-GELU MLP, multi-head attention), each
through the loss, its gradient and the last position's logits, with the
program's parameters in float32 so that both compute alike; and the
reference's list of the weights a configuration states."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.reference import dense
from bench.tests.tiny import PAPER, tiny_cell

MODELS = ["qwen3", "paper"]


def _conf(which):
    if which == "qwen3":
        return tiny_cell("qwen3-4b.serve.chat").config
    return {"source": "https://arxiv.org/abs/2105.14450", "config": PAPER}


def _setup(which):
    from repro.core.topology import single_device_layout
    from repro.models import transformer
    conf = _conf(which)
    cfg = spec.model_config(conf, which)
    layout = single_device_layout()
    p = weights.make(transformer.abstract_params(cfg, layout), layout, 7,
                     0.2)
    p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    # norm gains away from 1 so that a misplaced gain shows
    p = jax.tree_util.tree_map_with_path(
        lambda path, x: x * (1.0 + 0.1 * jnp.arange(x.shape[-1]) / x.shape[-1])
        if str(path[-1]) in ("['g']", "['q_norm']", "['k_norm']") else x, p)
    tok = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab)
    return conf["config"], cfg, layout, p, tok


@pytest.mark.parametrize("which", MODELS)
def test_loss_and_gradient_match_the_program(which):
    from repro.models import transformer
    model, cfg, layout, p, tok = _setup(which)
    lab = jnp.roll(tok, -1, axis=1).at[:, -1].set(-1)

    def prog(pp):
        return transformer.forward(cfg, layout, pp,
                                   {"tokens": tok, "labels": lab},
                                   mode="train")[0]

    def ref(pp):
        lg = dense.logits(model, pp, tok)
        picked = jnp.take_along_axis(lg, jnp.maximum(lab, 0)[..., None],
                                     -1)[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        nll = jax.nn.logsumexp(lg, axis=-1) - picked
        return jnp.sum(nll * mask) / jnp.sum(mask)

    lp, gp = jax.jit(jax.value_and_grad(prog))(p)
    lr, gr = jax.jit(jax.value_and_grad(ref))(p)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("which", MODELS)
def test_prefill_logits_match_the_program(which):
    from repro.models import transformer
    model, cfg, layout, p, tok = _setup(which)
    length = jnp.array([24, 17], jnp.int32)
    lp, _ = jax.jit(lambda pp: transformer.prefill(
        cfg, layout, pp, {"tokens": tok, "length": length}))(p)
    lr = dense.logits(model, p, tok)
    want = jnp.stack([lr[0, 23], lr[1, 16]])
    np.testing.assert_allclose(np.asarray(lp), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("which", MODELS)
def test_leaves_off_lists_what_the_configuration_does_not_state(which):
    model, _, _, p, _ = _setup(which)
    assert dense.leaves_off(model, p) == []
    if which == "qwen3":
        # Qwen3 states qk-norm by its model_type and a gated MLP: a tree
        # without them departs from it, whatever the reference could run
        dropped = jax.tree.map(lambda x: x, p)
        del dropped["stack"]["dense"]["attn"]["q_norm"]
        del dropped["stack"]["dense"]["mlp"]["w_gate"]
        assert dense.leaves_off(model, dropped) == [
            "stack/dense/attn/q_norm", "stack/dense/mlp/w_gate"]
    else:
        assert dense.leaves_off(dict(model, qk_norm=True), p) == [
            "stack/dense/attn/k_norm", "stack/dense/attn/q_norm"]
