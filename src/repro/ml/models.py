"""The paper's six predictive-learning algorithms (§4.1.3), in JAX:

  GLM     logistic regression (Newton-damped Adam)
  Tree    single oblivious decision tree (variance/Gini criterion)
  CTree   conditional-inference-style tree (t-statistic-normalised gain)
  RF      random forest of oblivious trees (bagging + feature subsampling
          via per-tree bins), majority/mean vote
  Boost   gradient boosting (logistic loss, depth-3 oblivious trees)
  NN      one-hidden-layer MLP

All expose fit(X, y) / predict_proba(X) with numpy in/out; training math runs in
JAX.  Standardisation is folded into fit."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.ml.forest import ForestParams, fit_oblivious_forest, forest_predict


def _standardize_fit(X):
    mu = X.mean(0)
    sd = X.std(0) + 1e-6
    return mu, sd


class BaseModel:
    name = "base"

    def fit(self, X, y):
        raise NotImplementedError

    def predict_proba(self, X):
        raise NotImplementedError

    def predict(self, X, threshold=0.5):
        return (self.predict_proba(X) >= threshold).astype(np.float32)


# ---------------------------------------------------------------------------
# GLM
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps",))
def _glm_fit(X, y, steps: int = 200, lr: float = 0.3):
    N, F = X.shape
    wb = jnp.zeros((F + 1,))
    Xb = jnp.concatenate([X, jnp.ones((N, 1))], axis=1)

    def loss(wb):
        z = Xb @ wb
        return jnp.mean(jnp.logaddexp(0.0, z) - y * z) + 1e-4 * jnp.sum(wb * wb)

    g = jax.grad(loss)

    def step(carry, _):
        wb, m, v, t = carry
        gr = g(wb)
        t = t + 1
        m = 0.9 * m + 0.1 * gr
        v = 0.999 * v + 0.001 * gr * gr
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        wb = wb - lr * mh / (jnp.sqrt(vh) + 1e-8)
        return (wb, m, v, t), None

    (wb, _, _, _), _ = jax.lax.scan(step, (wb, jnp.zeros_like(wb),
                                           jnp.zeros_like(wb), 0.0),
                                    length=steps)
    return wb


class GLM(BaseModel):
    name = "Glm"

    def fit(self, X, y):
        self.mu, self.sd = _standardize_fit(X)
        Xs = jnp.asarray((X - self.mu) / self.sd)
        self.wb = _glm_fit(Xs, jnp.asarray(y))
        return self

    def predict_proba(self, X):
        Xs = (X - self.mu) / self.sd
        z = Xs @ np.asarray(self.wb[:-1]) + float(self.wb[-1])
        return 1.0 / (1.0 + np.exp(-z))


# ---------------------------------------------------------------------------
# Trees / forest
# ---------------------------------------------------------------------------

class Tree(BaseModel):
    name = "Tree"
    criterion = "var"
    n_trees = 1
    depth = 6

    def fit(self, X, y):
        self.params = fit_oblivious_forest(
            X, y, n_trees=self.n_trees, depth=self.depth, n_bins=16,
            bootstrap=False,
            criterion=self.criterion)
        return self

    def predict_proba(self, X):
        return np.clip(forest_predict(self.params, X), 0.0, 1.0)


class CTree(Tree):
    name = "CTree"
    criterion = "ctree"


class RandomForest(BaseModel):
    name = "R.F."

    def __init__(self, n_trees=24, depth=5, n_bins=8, seed=0):
        self.n_trees, self.depth, self.n_bins, self.seed = \
            n_trees, depth, n_bins, seed

    def fit(self, X, y):
        self.params = fit_oblivious_forest(
            X, y, n_trees=self.n_trees, depth=self.depth, n_bins=self.n_bins,
            bootstrap=True, seed=self.seed)
        return self

    def predict_proba(self, X):
        return np.clip(forest_predict(self.params, X), 0.0, 1.0)


class Boost(BaseModel):
    """Gradient boosting with logistic loss and shallow oblivious trees."""
    name = "Boost"

    def __init__(self, rounds=20, depth=3, lr=0.3, n_bins=8):
        self.rounds, self.depth, self.lr, self.n_bins = rounds, depth, lr, n_bins

    def fit(self, X, y):
        N = X.shape[0]
        score = np.zeros(N, np.float32)
        self.stages: list[ForestParams] = []
        prior = float(np.clip(y.mean(), 1e-3, 1 - 1e-3))
        self.bias = float(np.log(prior / (1 - prior)))
        score += self.bias
        for r in range(self.rounds):
            p = 1.0 / (1.0 + np.exp(-score))
            resid = (y - p).astype(np.float32)       # negative gradient
            hess = np.maximum(p * (1 - p), 1e-3).astype(np.float32)
            # weighted least squares on resid/hess with weight hess:
            stage = fit_oblivious_forest(
                X, resid / hess, n_trees=1, depth=self.depth, n_bins=self.n_bins,
                bootstrap=False, sample_weight=hess, seed=r)
            self.stages.append(stage)
            score += self.lr * forest_predict(stage, X)
        return self

    def predict_proba(self, X):
        score = np.full(X.shape[0], self.bias, np.float32)
        for stage in self.stages:
            score += self.lr * forest_predict(stage, X)
        return 1.0 / (1.0 + np.exp(-score))


# ---------------------------------------------------------------------------
# Neural network
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("steps", "hidden"))
def _nn_fit(X, y, key, steps: int = 400, hidden: int = 32, lr: float = 3e-3):
    N, F = X.shape
    k1, k2 = jax.random.split(key)
    params = {
        "w1": jax.random.normal(k1, (F, hidden)) / jnp.sqrt(F),
        "b1": jnp.zeros((hidden,)),
        "w2": jax.random.normal(k2, (hidden, 1)) / jnp.sqrt(hidden),
        "b2": jnp.zeros((1,)),
    }

    def fwd(p, X):
        h = jnp.tanh(X @ p["w1"] + p["b1"])
        return (h @ p["w2"] + p["b2"])[:, 0]

    def loss(p):
        z = fwd(p, X)
        return jnp.mean(jnp.logaddexp(0.0, z) - y * z)

    g = jax.grad(loss)

    def step(carry, _):
        p, m, v, t = carry
        gr = g(p)
        t = t + 1
        m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, gr)
        v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, v, gr)
        p = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - 0.9 ** t))
            / (jnp.sqrt(v / (1 - 0.999 ** t)) + 1e-8), p, m, v)
        return (p, m, v, t), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _, _), _ = jax.lax.scan(step, (params, zeros, zeros, 0.0),
                                        length=steps)
    return params


class NeuralNet(BaseModel):
    name = "N.N."

    def fit(self, X, y):
        self.mu, self.sd = _standardize_fit(X)
        Xs = jnp.asarray((X - self.mu) / self.sd)
        self.params = _nn_fit(Xs, jnp.asarray(y), jax.random.PRNGKey(0))
        return self

    def predict_proba(self, X):
        Xs = (X - self.mu) / self.sd
        p = self.params
        h = np.tanh(Xs @ np.asarray(p["w1"]) + np.asarray(p["b1"]))
        z = (h @ np.asarray(p["w2"]) + np.asarray(p["b2"]))[:, 0]
        return 1.0 / (1.0 + np.exp(-z))


def forest_shape(algo: str) -> tuple[int, int] | None:
    """(n_trees, depth) of a single-forest algo (Tree / CTree / R.F.) — the
    shape its models take in a grouped flush — else None."""
    model = ALL_MODELS[algo]()
    if isinstance(model, (Tree, RandomForest)):
        return model.n_trees, model.depth
    return None


ALL_MODELS = {
    "Tree": Tree, "Boost": Boost, "Glm": GLM, "CTree": CTree,
    "R.F.": RandomForest, "N.N.": NeuralNet,
}
