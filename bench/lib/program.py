"""How the benchmark names the program's pieces: the one place that turns a
configuration file into the program's own configuration object."""
from __future__ import annotations


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file's sizes."""
    from repro.models.common import ModelConfig
    return ModelConfig(
        name=cfg["name"], family=cfg["family"], n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"], norm=cfg["norm"],
        act=cfg["act"], pos=cfg["pos"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["norm_eps"], tie_embeddings=cfg["tie_embeddings"])
