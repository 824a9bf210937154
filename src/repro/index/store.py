"""IndexStore: the persistent, mutable corpus container behind the batched
BMO-NN index service (DESIGN.md §3).

One store owns everything the paper's Algorithm 2 recomputes per call:
  * the padded/blocked corpus layout (dense), the cached Hadamard rotation
    (sign vector + pre-rotated corpus — only *queries* are rotated at request
    time, §IV-B amortized), or the padded-CSR sparse layout (§IV-A),
  * per-arm block-statistics priors (running mean/variance of the corpus
    rows' block values) used to warm-start RaceState confidence intervals,
  * a tombstone ``alive`` mask so deletes are O(1) and inserts reuse free
    slots — dead slots enter every race pre-rejected (mutable.py).

Arrays are capacity-padded (slots ≥ live points) so that mutation does not
change traced shapes until a genuine growth, keeping the jitted batched-race
executable warm across inserts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BMOConfig

KINDS = ("dense", "rotated", "sparse")


@dataclasses.dataclass
class IndexStore:
    kind: str                         # dense | rotated | sparse
    cfg: BMOConfig                    # racing defaults bound at build time
    d: int                            # true dimension (sparse θ normalizer;
                                      # dense θ is per d_pad coordinate)
    alive: jax.Array                  # (cap,) bool — tombstone mask
    # --- dense / rotated layout ---
    x: Optional[jax.Array] = None     # (cap, d_pad) float32, blocked layout
    block: int = 128
    signs: Optional[jax.Array] = None # (d_pad,) ±1 — cached §IV-B rotation
    # --- sparse (padded-CSR) layout ---
    indices: Optional[jax.Array] = None  # (cap, m) int32, sorted, pad = d
    values: Optional[jax.Array] = None   # (cap, m) float32
    nnz: Optional[jax.Array] = None      # (cap,) int32
    # --- block-statistics priors (builder.py) ---
    prior_var: Optional[jax.Array] = None  # (cap,) per-arm block-value variance
    prior_weight: float = 4.0              # pseudo-observations for warm-start

    @property
    def capacity(self) -> int:
        return int(self.alive.shape[0])

    @property
    def n_live(self) -> int:
        # cached per instance: this sits on the per-decode-step serving path
        # (index_knn's k guard) and a device sync per call would serialize
        # host and device. Mutations build new instances (dataclasses.replace)
        # so the cache invalidates itself.
        if "_n_live" not in self.__dict__:
            self._n_live = int(jnp.sum(self.alive))
        return self._n_live

    @property
    def d_pad(self) -> int:
        assert self.x is not None
        return self.x.shape[1]

    @property
    def n_blocks(self) -> int:
        return self.d_pad // self.block

    @property
    def m(self) -> int:
        assert self.indices is not None
        return self.indices.shape[1]

    # -- query-side preprocessing ------------------------------------------

    def prepare_queries(self, queries) -> jax.Array:
        """Dense/rotated: pad (and rotate, using the *cached* signs) a (Q, d)
        query batch into the store's (Q, d_pad) layout."""
        assert self.kind in ("dense", "rotated")
        qs = jnp.asarray(queries, jnp.float32)
        pad = self.d_pad - qs.shape[-1]
        if pad:
            qs = jnp.pad(qs, [(0, 0)] * (qs.ndim - 1) + [(0, pad)])
        if self.kind == "rotated":
            from repro.kernels import ops as kops
            qs = kops.fwht(qs * self.signs[None, :])
        return qs

    def query(self, queries, rng: jax.Array, *, k: Optional[int] = None,
              impl: str = "auto"):
        """Batched k-NN of (Q, d) dense queries — or a (q_idx, q_val, q_nnz)
        padded triplet for the sparse box — against the live corpus.
        Returns an index.batched_race.BatchedKNNResult with slot indices."""
        from repro.index import batched_race
        return batched_race.index_knn(self, queries, rng, k=k, impl=impl)

    # -- (de)serialization --------------------------------------------------

    def arrays(self) -> dict:
        """The array pytree that checkpoint/manager.py persists."""
        out = {"alive": self.alive}
        for name in ("x", "signs", "indices", "values", "nnz", "prior_var"):
            arr = getattr(self, name)
            if arr is not None:
                out[name] = arr
        return out

    def meta(self) -> dict:
        return {
            "kind": self.kind,
            "d": self.d,
            "block": self.block,
            "prior_weight": float(self.prior_weight),
            "cfg": dataclasses.asdict(self.cfg),
        }

    @classmethod
    def from_arrays(cls, arrays: dict, meta: dict) -> "IndexStore":
        cfg = BMOConfig(**meta["cfg"])
        return cls(
            kind=meta["kind"], cfg=cfg, d=int(meta["d"]),
            alive=jnp.asarray(arrays["alive"], bool),
            x=_opt(arrays, "x", jnp.float32),
            block=int(meta["block"]),
            signs=_opt(arrays, "signs", jnp.float32),
            indices=_opt(arrays, "indices", jnp.int32),
            values=_opt(arrays, "values", jnp.float32),
            nnz=_opt(arrays, "nnz", jnp.int32),
            prior_var=_opt(arrays, "prior_var", jnp.float32),
            prior_weight=float(meta.get("prior_weight", 4.0)),
        )


def _opt(arrays: dict, name: str, dtype):
    return jnp.asarray(arrays[name], dtype) if name in arrays else None


def free_slots(store: IndexStore) -> np.ndarray:
    """Host-side list of dead slot ids (insert targets), ascending."""
    return np.nonzero(~np.asarray(store.alive))[0]
