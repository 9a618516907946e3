"""The activations a Megatron tensor-parallel decode step allreduces
(arXiv:1909.08053 §3): ``batch`` rows of ``hidden_size`` for the
vocabulary-parallel input embedding, then, for each of the
``num_hidden_layers`` layers, its row-parallel mixer output projection and
its row-parallel MLP down projection, in the order a step issues them."""


def sizes(config: dict, itemsize: int) -> list[int]:
    rows = int(config["batch"]) * int(config["hidden_size"])
    return [rows] * (1 + 2 * int(config["num_hidden_layers"]))
