"""The sizes of one decoder-only transformer, as the benchmark's own code
reads them.  A family module (``benchmarks/families/<family>.py``) turns a
configuration file's published keys into one of these and into the program's
own config object; the reference, the cost functions and the seeded weights
read nothing else."""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
#: directories searched for configs/, workloads/ and layer_metrics/ files,
#: first hit wins; the tests append a directory of toy files
ROOTS = [ROOT]


@dataclasses.dataclass(frozen=True)
class Arch:
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    ffn: int
    vocab: int
    max_pos: int
    norm: str            # 'layer' | 'rms'
    act: str             # 'gelu_tanh' | 'swiglu'
    pos: str             # 'learned' | 'rope'
    eps: float
    rope_theta: float = 10000.0
    window: Optional[int] = None

    @property
    def gqa(self) -> bool:
        return self.kv_heads != self.heads

    def num_params(self) -> int:
        """Parameters as run (biases and an untied head included)."""
        D, F, V = self.dim, self.ffn, self.vocab
        dkv = self.kv_heads * self.head_dim
        attn = D * D + D + 2 * (D * dkv + dkv) + D * D + D
        mlp = (3 * D * F + 2 * F + D) if self.act == "swiglu" else (2 * D * F + F + D)
        n = D if self.norm == "rms" else 2 * D
        pos = self.max_pos * D if self.pos == "learned" else 0
        return V * D + pos + self.layers * (attn + mlp + 2 * n) + n + D * V

    def matmul_params(self) -> int:
        """Weights that a token is multiplied by: the matrices of the blocks
        and the output head (not the embedding tables, norms or biases)."""
        D, F = self.dim, self.ffn
        dkv = self.kv_heads * self.head_dim
        mlp = 3 * D * F if self.act == "swiglu" else 2 * D * F
        return self.layers * (2 * D * D + 2 * D * dkv + mlp) + D * self.vocab


def find_file(*parts: str) -> str:
    for root in ROOTS:
        path = os.path.join(root, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(os.path.join(*parts))


def load_json(*parts: str) -> Dict[str, Any]:
    with open(find_file(*parts)) as f:
        return json.load(f)


def load_config(name: str) -> Dict[str, Any]:
    cfg = load_json("configs", f"{name}.json")
    if cfg.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def family_of(cfg: Dict[str, Any]):
    """The module ``benchmarks/families/<family>.py`` that the configuration
    file names; a new family is a new file, nothing here lists them."""
    return importlib.import_module(f"benchmarks.families.{cfg['family']}")
