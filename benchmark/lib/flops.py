"""Operations a GPT-2-style decoder needs, computed from its shapes.

`model` is a configuration file's content: hidden_size (H),
num_hidden_layers (L), intermediate_size (I), vocab_size (V).

Matmul parameters: per layer QKV H*3H, attention output H*H, MLP H*I and
I*H; plus the tied output head V*H, counted once as a matmul.  The token
and position look-ups, biases and LayerNorms are not matmuls and are not
counted.

A token costs 2 operations per matmul parameter forward and 4 backward.
Causal attention adds, per layer and per token of a sequence of S tokens,
QK^T and PV over on average S/2 keys: 2 * 2 * (S/2) * H = 2*S*H forward,
three times that forward and backward.  Recomputation is not counted.
"""


def matmul_params(model):
    h, i = model["hidden_size"], model["intermediate_size"]
    per_layer = h * 3 * h + h * h + h * i + i * h
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * h


def total_params(model):
    """Every parameter of the model as the program builds it (embeddings,
    positions, biases and LayerNorms included)."""
    h, i = model["hidden_size"], model["intermediate_size"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * i + i) \
        + (i * h + h) + 4 * h
    return (model["num_hidden_layers"] * per_layer
            + model["vocab_size"] * h
            + model["max_position_embeddings"] * h + 2 * h)


def train_flops_per_token(model, seq_len):
    return (6 * matmul_params(model)
            + 6 * model["num_hidden_layers"] * seq_len
            * model["hidden_size"])


def forward_flops(model, seq_len):
    """One forward pass over one sequence of `seq_len` tokens."""
    return seq_len * (2 * matmul_params(model)
                      + 2 * model["num_hidden_layers"] * seq_len
                      * model["hidden_size"])
