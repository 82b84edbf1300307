"""Unit/property tests for model building blocks."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, list_archs
from repro.configs.base import SHAPES, input_specs
from repro.models import model as M
from repro.models.layers import cross_entropy, rms_norm, rope
from repro.models.param import abstract_params
from repro.models.sharding import spec_for

rng = np.random.default_rng(0)
SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------- layers
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(1, 32), st.sampled_from([8, 32, 128]))
def test_rmsnorm_scale_invariance(B, S, D):
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    scale = jnp.zeros((D,))
    out = rms_norm(x, scale)
    # unit RMS per position
    rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)
    # positive homogeneity: rms_norm(c*x) == rms_norm(x)
    out2 = rms_norm(3.7 * x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-4)


def test_rope_preserves_norm_and_relative_angle():
    hd = 64
    q = jnp.asarray(rng.normal(size=(1, 8, 2, hd)), jnp.float32)
    pos = jnp.arange(8)[None]
    r = rope(q, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(q), axis=-1),
                               np.linalg.norm(np.asarray(r), axis=-1),
                               rtol=1e-5)
    # dot(q_i, k_j) after rope depends only on i-j
    k = jnp.asarray(rng.normal(size=(1, 8, 2, hd)), jnp.float32)
    qr, kr = rope(q, pos, 1e4), rope(k, pos, 1e4)
    d1 = float(jnp.einsum("d,d->", qr[0, 3, 0], kr[0, 1, 0]))
    q2, k2 = rope(q, pos + 17, 1e4), rope(k, pos + 17, 1e4)
    d2 = float(jnp.einsum("d,d->", q2[0, 3, 0], k2[0, 1, 0]))
    assert abs(d1 - d2) < 1e-3


def test_cross_entropy_uniform_logits():
    V = 64
    logits = jnp.zeros((2, 3, V))
    labels = jnp.asarray(rng.integers(0, V, size=(2, 3)))
    loss = float(cross_entropy(logits, labels))
    assert abs(loss - np.log(V)) < 1e-5


def test_cross_entropy_mask():
    V = 16
    logits = jnp.zeros((1, 4, V))
    labels = jnp.zeros((1, 4), jnp.int32)
    mask = jnp.asarray([[1, 1, 0, 0]], jnp.float32)
    loss = float(cross_entropy(logits, labels, mask))
    assert abs(loss - np.log(V)) < 1e-5


# ---------------------------------------------------------------- params
def test_param_init_deterministic_and_path_stable():
    cfg = get_config("granite-3-2b").reduced()
    p1 = M.init_model_params(cfg, jax.random.PRNGKey(7))
    p2 = M.init_model_params(cfg, jax.random.PRNGKey(7))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert bool(jnp.all(a == b))


def test_param_init_same_in_every_process():
    """Weights follow the seed alone: processes with differently salted
    str hashes (cluster workers) draw the same parameters."""
    code = ("import jax, numpy as np\n"
            "from repro.configs import get_config\n"
            "from repro.models import model as M\n"
            "p = M.init_model_params(get_config('granite-3-2b-smoke'),\n"
            "                        jax.random.PRNGKey(0))\n"
            "print(repr(sum(float(np.abs(np.asarray(l, np.float64)).sum())\n"
            "               for l in jax.tree.leaves(p))))\n")
    sums = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu",
                   PYTHONPATH=SRC)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        sums.append(out.stdout.strip())
    assert sums[0] == sums[1]


def test_smoke_arch_ids_resolve_to_reduced_configs():
    full = get_config("granite-3-2b")
    assert (full.n_layers, full.d_model, full.dtype) == (40, 2048,
                                                         "bfloat16")
    smoke = get_config("granite-3-2b-smoke")
    assert smoke == full.reduced() and smoke.name == "granite-3-2b-smoke"
    with pytest.raises(KeyError):
        get_config("no-such-arch-smoke")


def test_abstract_params_match_init_shapes():
    cfg = get_config("qwen2.5-14b").reduced()
    specs = M.param_specs(cfg)
    abstract = abstract_params(specs, cfg.dtype)
    concrete = M.init_model_params(cfg, jax.random.PRNGKey(0))
    ab = jax.tree.leaves(abstract)
    co = jax.tree.leaves(concrete)
    assert len(ab) == len(co)
    for a, c in zip(ab, co):
        assert a.shape == c.shape and a.dtype == c.dtype


def test_n_params_counts_full_configs():
    # coarse sanity on the advertised sizes (within 40%)
    expect = {"deepseek-7b": 7e9, "qwen2.5-14b": 14e9,
              "mistral-large-123b": 123e9, "grok-1-314b": 314e9}
    for arch, n in expect.items():
        cfg = get_config(arch)
        assert 0.6 * n < cfg.n_params < 1.45 * n, (arch, cfg.n_params)


def test_moe_active_params_less_than_total():
    cfg = get_config("grok-1-314b")
    assert cfg.n_active_params < cfg.n_params
    # top-2 of 8 experts -> ~2/8 of expert params + shared
    assert cfg.n_active_params > cfg.n_params * 2 / 8 * 0.8


def test_padded_vocab_divisibility():
    for arch in list_archs():
        cfg = get_config(arch)
        assert cfg.padded_vocab >= cfg.vocab
        if cfg.vocab > 1024:
            assert cfg.padded_vocab % 256 == 0


# ---------------------------------------------------------------- sharding
class _FakeMesh:
    def __init__(self, shape, names):
        import numpy as _np
        self.devices = _np.empty(shape)
        self.axis_names = names


def test_spec_for_divisibility_fallback():
    mesh = _FakeMesh((4, 8), ("data", "model"))
    rules = {"batch": "data", "heads": "model"}
    # divisible -> sharded
    s = spec_for((16, 64), ("batch", "heads"), rules, mesh)
    assert tuple(s) == ("data", "model")
    # head dim not divisible by 8 -> replicated
    s = spec_for((16, 6), ("batch", "heads"), rules, mesh)
    assert tuple(s) == ("data",)
    # same mesh axis never used twice
    rules2 = {"a": "model", "b": "model"}
    s = spec_for((8, 8), ("a", "b"), rules2, mesh)
    assert tuple(s) == ("model",)


def test_input_specs_cover_modalities():
    for arch, key in [("whisper-tiny", "frames"), ("llava-next-34b",
                                                   "patches")]:
        cfg = get_config(arch)
        sp = input_specs(cfg, SHAPES["train_4k"])
        assert key in sp and sp[key].shape[-1] == cfg.d_model
        sp_dec = input_specs(cfg, SHAPES["decode_32k"])
        assert sp_dec["tokens"].shape == (128, 1)
