"""Hands the ``pyvertical-mnist`` configuration and its images to the
program: a ``repro`` ``MLPSplitConfig`` with the sizes of
``pyvertical-mnist.json``, two owners holding the left and the right
half of every image for a random 90% of the entities each, and the
scientist holding every label."""
from __future__ import annotations

import numpy as np


def program_config(cfg: dict):
    from repro.configs.base import SplitConfig
    from repro.configs.pyvertical_mnist import MLPSplitConfig
    return MLPSplitConfig(
        n_features=cfg["n_features"], n_classes=cfg["n_classes"],
        head_layers=tuple(cfg["head_layers"]),
        trunk_layers=tuple(cfg["trunk_layers"]),
        batch_size=cfg["batch_size"], n_train=cfg["n_train"],
        split=SplitConfig(n_owners=cfg["n_owners"], cut_layer=1,
                          combine=cfg["combine"],
                          cut_dim=cfg["head_layers"][-1],
                          owner_lr=cfg["owner_lr"],
                          scientist_lr=cfg["scientist_lr"]))


class Data:
    def __init__(self, cfg: dict, traffic: dict, seed: int, reference):
        from repro.federation import DataOwner, DataScientist
        n, P = traffic["n_entities"], cfg["n_owners"]
        X, y = reference.make_data(cfg, n, seed)
        side = int(round(np.sqrt(cfg["n_features"])))
        # image columns split into P contiguous bands (left/right halves)
        bands = np.split(X.reshape(n, side, side), P, axis=-1)
        halves = [b.reshape(n, -1) for b in bands]
        self.ids = [f"subject-{i:08d}" for i in range(n)]
        rng = np.random.default_rng([seed, 1])
        kept = []
        self.owners = []
        for p in range(P):
            idx = np.flatnonzero(rng.random(n) < traffic["keep_frac"])
            rng.shuffle(idx)
            kept.append(set(idx.tolist()))
            self.owners.append(DataOwner(
                f"owner{p}", [self.ids[i] for i in idx], halves[p][idx]))
        self.scientist = DataScientist(self.ids, y)
        shared = set.intersection(*kept)
        self.aligned = sorted(self.ids[i] for i in shared)
        self.rows = np.array(sorted(shared))     # id order == index order
        self.halves, self.labels = halves, y

    def reference_batch(self, positions):
        r = self.rows[np.asarray(positions)]
        return np.stack([h[r] for h in self.halves], axis=1), self.labels[r]


def units_per_step(cfg: dict, traffic: dict) -> dict:
    return {"samples": traffic["batch"]}
