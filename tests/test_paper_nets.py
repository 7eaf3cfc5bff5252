"""The paper's three nets at their published widths: event path == dense.

``configs.PAPER_SPECS`` (mnist, svhn, cifar10) through ``engine.infer_batch``
on ``queue_pallas`` (off the chip, the fused XLA realization of the event
kernel) against ``dense`` (T-batched XLA convs), for every neuron mode and
both input encodings, on two seeded images. This covers every spike-fed
stage of the served paper nets at full width, svhn's one-channel stage
(``1C3``, whose output feeds a 32-channel conv) included.

Tolerances as in ``test_vgg16.py``: logits to rtol/atol 1e-4, since the
event path may sum a membrane's charge in another order than a conv;
``events_in`` exactly, layer by layer. The default queue depth (256) is at
least every phase segment's window count, so nothing is dropped and
``dense`` is an oracle for the event counts.
"""
import jax
import numpy as np
import pytest

from repro import configs
from repro.core import engine, neuron, snn_model


@pytest.fixture(scope="module")
def nets():
    """Seeded weights, unit thresholds and two images for each paper net."""
    out = {}
    for name, d in configs.PAPER_SPECS.items():
        params = snn_model.init_params(jax.random.PRNGKey(0), d["spec"],
                                       d["hw"], d["c"])
        th = [1.0] * len(engine.parse_spec(d["spec"]))
        images = jax.random.uniform(jax.random.PRNGKey(1),
                                    (2, d["hw"], d["hw"], d["c"]))
        out[name] = params, th, images
    return out


@pytest.mark.parametrize("input_mode", ["analog", "binary"])
@pytest.mark.parametrize("mode", neuron.MODES)
@pytest.mark.parametrize("net", sorted(configs.PAPER_SPECS))
def test_paper_net_event_path_matches_dense(nets, make_snn_config, net, mode,
                                            input_mode):
    d = configs.PAPER_SPECS[net]
    params, th, images = nets[net]
    cfg = make_snn_config(d["spec"], d["hw"], d["c"], depth=256, T=4,
                          mode=mode, input_mode=input_mode)
    le, se = engine.infer_batch(params, th, cfg, images,
                                backend="queue_pallas")
    ld, sd = engine.infer_batch(params, th, cfg, images, backend="dense")
    np.testing.assert_allclose(np.asarray(le), np.asarray(ld),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(se.events_in),
                                  np.asarray(sd.events_in))
    assert not np.asarray(se.overflow).any()
    # every spike-fed layer sees events, so the comparison is not vacuous
    assert (np.asarray(sd.events_in)[:, 1:] > 0).all()
