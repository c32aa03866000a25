"""``distilbert_sent140``'s own contract: the departures its file states are
the program's, and the model the program builds for its task is the tree,
the head and the parameter count the file and its reference describe."""

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

import config_contract
from benchmark import manifest

NAME = "distilbert_sent140"


def test_the_program_builds_the_encoder_the_file_states():
    config, task_model, spec = config_contract.load(NAME)
    stated = config["model"]
    assert set(config["model_keys"]) == {
        "dim", "n_layers", "n_heads", "hidden_dim", "vocab_size",
        "max_position_embeddings", "num_classes"}
    assert list(task_model["input_shape"]) == [stated["sequence_length"]]
    shapes = config_contract.init_shapes(spec, task_model)
    W, M = stated["dim"], stated["hidden_dim"]
    assert shapes["Embed_0/embedding"] == (stated["vocab_size"], W)
    assert shapes["pos_embedding"] == (
        1, stated["max_position_embeddings"], W)
    blocks = {k.split("/")[0] for k in shapes
              if k.startswith("TransformerBlock_")}
    assert len(blocks) == stated["n_layers"]
    assert shapes["TransformerBlock_0/Dense_0/kernel"] == (W, M)
    assert shapes["TransformerBlock_0/MultiHeadDotProductAttention_0/"
                  "query/kernel"] == (W, stated["n_heads"],
                                      W // stated["n_heads"])
    # The head the file states: pooled vector -> Dense(num_classes),
    # and no pre_classifier layer.
    assert stated["head"] == "mean_pool_dense"
    top = {k.split("/")[0] for k in shapes}
    assert top == blocks | {"Embed_0", "pos_embedding", "LayerNorm_0",
                            "Dense_0"}
    assert shapes["Dense_0/kernel"] == (W, stated["num_classes"])
    # The published encoder's 66,362,880 parameters, less the position
    # rows cut, plus this head.
    assert sum(int(np.prod(s)) for s in shapes.values()) == (
        66_362_880 - (512 - stated["max_position_embeddings"]) * W
        + W * stated["num_classes"] + stated["num_classes"])


def test_the_departures_the_file_states_are_the_programs():
    config, _, _ = config_contract.load(NAME)
    stated = config["model"]
    assert stated["layer_norm_eps"] == nn.LayerNorm().epsilon
    assert stated["activation"] == "gelu_tanh"
    x = jnp.linspace(-3, 3, 13)
    reference = manifest.find_module("reference", config["reference"])
    np.testing.assert_allclose(nn.gelu(x), reference._gelu_tanh(x),
                               rtol=1e-6, atol=1e-7)
