"""What the benchmark takes from the program: its model, PEFT, ``Trainer``
and ``ServeEngine`` entry points, configured from a configuration file of
``bench/configs``. Nothing of the yardstick lives here."""

from __future__ import annotations

import sys

from bench.common import SRC


def import_program():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"bench: no program under {SRC}: run from a checkout of the repo")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(cfg: dict):
    """The program's ModelConfig for ``cfg``: the registered architecture
    with every size taken from the file."""
    from repro.configs import get_config

    return get_config(cfg["arch"]).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], qkv_bias=True,
        dtype=cfg["torch_dtype"], head_dim=0,
    )


def model(cfg: dict):
    from repro.models import get_model

    return get_model(model_config(cfg))
