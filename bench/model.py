"""A configuration file (``configs/<name>.json``, Hugging Face keys as
run) turned into the program's ``ModelConfig``.

The Hugging Face keys that name a ``ModelConfig`` field are mapped to it;
the file's ``program`` group then gives any other ``ModelConfig`` field by
its own name (family, dtypes, remat, and for other models ``kv_quant``,
``attention``, expert counts and so on), so a new configuration is a new
file and never an edit here."""
from __future__ import annotations

import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

# Hugging Face key -> ModelConfig field
HF_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings", "norm": "norm",
}


def load_config(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        conf = json.load(f)
    conf["name"] = name
    return conf


def model_config(conf: dict):
    from repro.configs.base import ModelConfig

    fields = {f: conf[k] for k, f in HF_FIELDS.items()}
    fields.update(conf["program"])
    return ModelConfig(name=conf["name"], **fields)


def reference(conf):
    """The plain reference module named by the configuration, which sits
    beside it in ``configs/``."""
    import importlib.util

    path = CONFIG_DIR / f"{conf['reference']}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{conf['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
