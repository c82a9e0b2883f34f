"""The float32 reference forward (models/reference.py) against the served
model's prefill logits on seeded weights, at reduced widths on the CPU —
and the tolerance's teeth: lower precision or a dropped layer must fail."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.models import build_model
from repro.models.inputs import make_dummy_batch
from repro.models.reference import (
    LOGIT_REL_TOL,
    logit_rel_error,
    reference_last_logits,
)

ARCHS = ("tinyllama-1.1b", "internvl2-76b")


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    cfg = get_config(request.param).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("ref", 32, 2, "train"))
    ref = reference_last_logits(cfg, params, batch)
    return cfg, model, params, batch, ref


def test_prefill_logits_match_reference(setup):
    cfg, model, params, batch, ref = setup
    last, _ = model.prefill(params, batch, 64)
    assert last.shape == ref.shape == (2, cfg.vocab_size)
    assert logit_rel_error(last, ref) < LOGIT_REL_TOL


def test_tolerance_rejects_fp8_weights(setup):
    cfg, model, params, batch, ref = setup
    fp8 = {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype)
           for k, v in params["layers"].items()}
    last, _ = model.prefill({**params, "layers": fp8}, batch, 64)
    assert logit_rel_error(last, ref) > LOGIT_REL_TOL


def test_tolerance_rejects_missing_layer(setup):
    cfg, model, params, batch, ref = setup
    short = dataclasses.replace(cfg, n_layers=cfg.n_layers - 1)
    layers = {k: v[:-1] for k, v in params["layers"].items()}
    last, _ = build_model(short).prefill({**params, "layers": layers}, batch, 64)
    assert logit_rel_error(last, ref) > LOGIT_REL_TOL


def test_reference_rejects_unsupported_family():
    cfg = get_config("starcoder2-3b").reduced()
    with pytest.raises(NotImplementedError):
        reference_last_logits(cfg, {}, {})
