"""The plain reference, generic over architectures: its layer-by-layer
gradient against ``jax.grad`` of the whole loss on a toy architecture
of two segments, and qwen3's readings at the tiny size against those
pinned before the architecture moved to ``archs/qwen3.py``."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import feed, model_spec, reference, spec, tiny  # noqa: E402

PINNED = os.path.join(HERE, "fixtures", "qwen3_tiny_readings.json")
SEED = 31


def tiny_readings(workload):
    """reference.run of the cell's tiny configuration on ``SEED``, with
    arrivals that put a different worker last on each step."""
    cell = tiny.cell(workload)
    t = feed.traffic(cell.traffic)
    data = feed.Feed(t, cell.config["vocab_size"], SEED)
    key = jax.random.PRNGKey(feed.derived_seed(SEED, 2, bits=32))
    arrivals = [np.roll(np.arange(t.total_workers, dtype=np.float64), i)
                for i in range(3)]
    return reference.run(cell.config, t, key,
                         [data.batch(i) for i in range(3)], arrivals)


def _close(got, want, norms, rel):
    """Every value within ``rel`` of max(its tensor's norm, the median
    tensor's), the measure ``check.py`` compares by."""
    med = float(np.median(list(norms.values())))
    assert sorted(got) == sorted(want)
    for name, w in want.items():
        gap = np.max(np.abs(np.asarray(got[name]) - np.asarray(w)))
        assert gap <= rel * max(norms[name], med), (name, got[name], w)


@pytest.mark.parametrize("workload", ["qwen3-0.6b.sim1.seq1024x1",
                                      "qwen3-0.6b.spmd4.seq64x4"])
def test_qwen3_readings_match_the_pinned_ones(workload):
    with open(PINNED) as f:
        want = json.load(f)["readings"][workload]
    got = tiny_readings(workload)
    assert got["losses"] == pytest.approx(want["losses"], rel=1e-6, abs=0)
    for group in ("grad", "param_change", "ema_change"):
        _close(got[group], want[group], want[group], 1e-6)
        _close(got[group + "_sketch"], want[group + "_sketch"], want[group],
               1e-6)


TOY = {"model_type": "toy_two_segments", "vocab_size": 97, "hidden_size": 32,
       "intermediate_size": 48, "num_hidden_layers": 5,
       "first_k_dense_replace": 2, "n_routed_experts": 4,
       "aux_loss_alpha": 0.5, "rms_norm_eps": 1e-6, "torch_dtype": "float32"}


def test_layer_by_layer_gradient_is_the_whole_loss_gradient():
    """Two segments with different layers (the second returns an
    auxiliary term), an untied head: the reference's gradient, taken
    segment by segment and layer by layer in reverse, is jax.grad of
    weight * (head loss + the auxiliary terms)."""
    arch = spec.arch("toy_two_segments", os.path.join(HERE, "fixtures"))
    s = model_spec.sizes(TOY)
    assert [k for k, _ in arch.segments(s)] == ["seg_dense", "seg_moe"]
    params = jax.jit(model_spec.init_fn(s))(jax.random.PRNGKey(7))
    # weights on the grid are small; scale them so every layer matters
    params = jax.tree_util.tree_map(lambda x: x * 16.0, params)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, s.vocab, (3, 11)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, s.vocab, (3, 11)), jnp.int32)
    weight, ident = 0.25, (lambda x: x)

    def whole(params):
        outer = {k: v for k, v in params.items() if not k.startswith("seg_")}
        x, aux = arch.embed(outer, tokens, s), 0.0
        for key, layer in arch.segments(s):
            count = jax.tree_util.tree_leaves(params[key])[0].shape[0]
            for i in range(count):
                out = layer(jax.tree_util.tree_map(lambda a: a[i],
                                                   params[key]), x, s, ident)
                x, a = out if isinstance(out, tuple) else (out, 0.0)
                aux = aux + a
        return weight * (arch.head_loss(outer, x, labels, s, ident) + aux), aux

    (want_loss, aux), want = jax.value_and_grad(whole, has_aux=True)(params)
    assert float(aux) > 1e-3
    grad_fn = jax.jit(functools.partial(reference._worker_grad, s=s,
                                        cast=ident))
    got, loss = grad_fn(reference.zeros(params), params, tokens, labels,
                        weight)
    assert float(loss) * weight == pytest.approx(float(want_loss), rel=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(w)) > 0, jax.tree_util.keystr(path)
        assert np.max(np.abs(g - w)) <= 1e-5 * np.max(np.abs(w)), \
            jax.tree_util.keystr(path)
