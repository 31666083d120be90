"""The general generators of traffic. A mix is a data file of parameters,
``benchmark/traffic/<traffic>.json``; a later cell brings a new file and no
code. Everything here is numpy from ``--seed``: the same seed gives the
same inputs. Every seed gives the same sizes in the same order (the batch
of a training mix; a serving mix's prompt and output lengths, see
``serve_requests``), and what the seed draws is the values: images, labels,
token ids, and the weights (``reference/*.py`` ``make_weights``).
"""

from __future__ import annotations

import json
import os

import numpy as np


def load(root: str, name: str, dry: bool = False) -> dict:
    with open(os.path.join(root, "benchmark", "traffic", name + ".json")) as f:
        mix = json.load(f)
    return with_dry(mix, dry)


def with_dry(d: dict, dry: bool) -> dict:
    """The file's sizes, or, for the CPU rehearsal, its ``dry_cpu`` sizes
    laid over them."""
    over = d.get("dry_cpu", {}) if dry else {}
    return {**{k: v for k, v in d.items() if k != "dry_cpu"}, **over}


# ---------------------------------------------------------------------------
# training: host batches that a feed cycles
# ---------------------------------------------------------------------------

def train_batches(cfg: dict, mix: dict, seed: int) -> list:
    """``host_batches`` pairs ``(features, labels, ids)``: float32 arrays as
    the program is fed them, and the whole numbers they were made from
    (``None`` for images), which is what the plain reference reads."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(mix["host_batches"]):
        if mix["data"] == "images":
            x = rng.standard_normal(
                (mix["batch"], cfg["height"], cfg["width"], cfg["channels"]),
                dtype=np.float32)
            cls = rng.integers(0, cfg["n_classes"], mix["batch"])
            y = np.zeros((mix["batch"], cfg["n_classes"]), np.float32)
            y[np.arange(mix["batch"]), cls] = 1.0
            out.append((x, y, None))
        elif mix["data"] == "tokens":
            B, T, V = mix["batch"], mix["seq_len"], cfg["vocab_size"]
            ids = rng.integers(0, V, (B, T + 1), dtype=np.int32)
            out.append((one_hot(ids[:, :-1], V), one_hot(ids[:, 1:], V),
                        (ids[:, :-1], ids[:, 1:])))
        else:
            raise ValueError(f"unknown training data {mix['data']!r}")
    return out


def one_hot(ids: np.ndarray, vocab: int) -> np.ndarray:
    """One-hot rows without an identity matrix."""
    out = np.zeros(ids.shape + (vocab,), np.float32)
    np.put_along_axis(out, ids[..., None], 1.0, axis=-1)
    return out


# ---------------------------------------------------------------------------
# serving: a pool of generate requests
# ---------------------------------------------------------------------------

def serve_lengths(mix: dict) -> list:
    """Every prompt length the mix can send, for the warm-up."""
    own, shared = mix["own_prompt_tokens"], mix["shared_prefix_tokens"]
    grid = own.get("grid", 1)
    return [shared + n for n in range(own["min"], own["max"] + 1)
            if n % grid == 0 or n in (own["min"], own["max"])]


def serve_requests(cfg: dict, mix: dict, seed: int) -> list:
    """``pool_requests`` requests ``{"tokens": [...], "max_new_tokens": n}``.
    The sizes (own prompt length heavy-tailed by a clipped log-normal,
    output length uniform) are drawn from a fixed stream, in a fixed order:
    a window sees some thirty admissions, and with the sizes in another
    order for each seed the tokens per second followed the seed (7 % from
    seed to seed, 0.2 % between two runs of one seed). The seed draws the
    ids, the shared instruction's among them."""
    sizes = np.random.default_rng(20240924)
    n = mix["pool_requests"]
    own = mix["own_prompt_tokens"]
    lens = np.exp(sizes.normal(np.log(own["median"]), own["sigma"], n))
    grid = own.get("grid", 1)      # lengths on a grid: see the mix's "what"
    lens = np.clip(np.rint(lens / grid) * grid, own["min"],
                   own["max"]).astype(int)
    outs = sizes.integers(mix["output_tokens"]["min"],
                          mix["output_tokens"]["max"] + 1, n)
    rng = np.random.default_rng(seed)
    V = cfg["vocab_size"]
    prefix = rng.integers(0, V, mix["shared_prefix_tokens"]).tolist()
    return [{"tokens": prefix + rng.integers(0, V, lens[i]).tolist(),
             "max_new_tokens": int(outs[i])} for i in range(n)]
