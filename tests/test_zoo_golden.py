"""Golden-topology tests for the model zoo (reference analog:
python/paddle/trainer_config_helpers/tests/configs/ golden-proto
comparisons + ProtobufEqualMain.cpp — a config helper change that
silently alters a topology must fail a diff against a committed
golden, not go unnoticed).

Each case builds a zoo model's parameter tree ABSTRACTLY (eval_shape —
no math runs) and compares names + shapes + total parameter count
against tests/golden/zoo_topology.json. Regenerate deliberately with:

    python tests/test_zoo_golden.py --regen
"""

import json
import math
import os
import sys

# must precede the paddle_tpu imports so the documented regen command
# (`python tests/test_zoo_golden.py --regen`) resolves the package when
# run from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import pytest

from paddle_tpu import models
from paddle_tpu.nn.module import ShapeSpec

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "zoo_topology.json")


def _layer(model, spec):
    # Layer-based zoo entries: init returns (params, state)
    return lambda rng: model.init(rng, spec)[0]


def _cases():
    from paddle_tpu.models import transformer as tf

    return {
        "lenet": _layer(models.lenet.lenet(10), ShapeSpec((4, 28, 28, 1))),
        "mlp": _layer(models.lenet.mlp(10, hidden=(64, 32)),
                      ShapeSpec((4, 28, 28, 1))),
        "smallnet": _layer(models.smallnet.smallnet(10),
                           ShapeSpec((4, 32, 32, 3))),
        "alexnet": _layer(models.alexnet.alexnet(num_classes=1000),
                          ShapeSpec((2, 224, 224, 3))),
        "googlenet": _layer(models.googlenet.googlenet(num_classes=1000),
                            ShapeSpec((2, 224, 224, 3))),
        "vgg19": _layer(models.vgg.vgg(19, num_classes=10),
                        ShapeSpec((2, 32, 32, 3))),
        "resnet18": _layer(models.resnet.resnet(18, num_classes=10),
                           ShapeSpec((2, 32, 32, 3))),
        "resnet50": _layer(models.resnet.resnet(50, num_classes=1000),
                           ShapeSpec((2, 224, 224, 3))),
        "text_lstm": lambda rng: models.text_lstm.init_params(
            rng, 1000, 2, embed_dim=32, hidden=64),
        "seq2seq_attn": lambda rng: models.seq2seq_attn.init_params(
            rng, 500, 600, embed_dim=32, hidden=48),
        "bow_lr": lambda rng: models.quick_start.init_bow_lr(rng, 1000),
        "text_cnn": lambda rng: models.quick_start.init_text_cnn(rng, 1000),
        "bidi_lstm": lambda rng: models.quick_start.init_bidi_lstm(rng, 1000),
        "transformer_small": lambda rng: tf.init_params(
            rng, tf.TransformerConfig(vocab=512, dim=64, n_layers=2,
                                      n_heads=4)),
        "transformer_moe": lambda rng: tf.init_params(
            rng, tf.TransformerConfig(vocab=512, dim=64, n_layers=2,
                                      n_heads=4, moe_experts=4)),
        "word2vec": lambda rng: models.word2vec.init_params(
            rng, 1000, embed_dim=32, hidden=64),
        "recommender": lambda rng: models.recommender.init_params(
            rng, models.recommender.RecommenderConfig(
                n_users=400, n_movies=600, title_vocab=256)),
        "srl_db_lstm": lambda rng: models.srl.init_params(
            rng, word_vocab=500, pred_vocab=50, num_labels=9, hidden=32),
    }


def _topology(build):
    params = jax.eval_shape(build, jax.random.key(0))
    flat = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        flat[name] = list(leaf.shape)
    return {
        "parameters": flat,
        "num_parameters": int(sum(
            math.prod(s) if s else 1 for s in flat.values())),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_zoo_topology_matches_golden(name):
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert name in golden, (
        f"no golden for {name}; regenerate: python {__file__} --regen")
    got = _topology(_cases()[name])
    exp = golden[name]
    assert got["parameters"] == exp["parameters"], (
        f"{name} topology drifted from golden "
        f"(regen deliberately if intended)")
    assert got["num_parameters"] == exp["num_parameters"]


if __name__ == "__main__":
    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump({name: _topology(b) for name, b in _cases().items()},
                      f, indent=1, sort_keys=True)
        print(f"wrote {GOLDEN}")
