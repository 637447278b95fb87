"""The paper's technique in the training data plane: distributed
coreset-based data selection, then training on the selected subset.

Flow: candidate pool sharded across (simulated) data-parallel sites ->
mean-pooled embedding per example -> Algorithm 1 over the embedding space
(ONE scalar communicated per site) -> weighted representative subset ->
train. Compares against training on a uniform random subset of equal size.

    PYTHONPATH=src python examples/coreset_data_selection.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import BigramLM, embed_examples, gather_selected, select_coreset
from repro.models import init_params
from repro.optim import adamw
from repro.train import TrainConfig, make_train_step
from repro.cache import enable_compilation_cache


def train_on(batches, cfg, steps=60, seed=0):
    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = adamw.init(params)
    tc = TrainConfig(peak_lr=1e-3, warmup_steps=5, total_steps=steps,
                     remat="none")
    step_fn = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    losses = []
    for s in range(steps):
        b = batches[s % len(batches)]
        params, opt, m = step_fn(params, opt, b, jnp.asarray(s, jnp.int32))
        losses.append(float(m["ce"]))
    return losses


def main():
    enable_compilation_cache()
    cfg = configs.get_reduced("llama3_8b")
    data = BigramLM(cfg.vocab_size, seed=0)
    n_sites, per_site, L, B = 4, 128, 64, 8

    pool = data.batch(0, n_sites * per_site, L)
    toks = np.asarray(pool["tokens"]).reshape(n_sites, per_site, L)
    labs = np.asarray(pool["labels"]).reshape(n_sites, per_site, L)

    # embed with a fresh model's embedding table (production would use the
    # current training state)
    params = init_params(jax.random.PRNGKey(0), cfg)
    emb = embed_examples(params["embed"]["table"], jnp.asarray(toks))
    sel = select_coreset(jax.random.PRNGKey(1), emb,
                         jnp.ones(emb.shape[:2], bool), k=8,
                         t=n_sites * per_site // 4)
    chosen = gather_selected(jnp.asarray(toks), sel)
    keep = np.asarray(chosen["weights"]) > 0
    sel_tok = np.asarray(chosen["tokens"])[keep]
    print(f"pool {n_sites * per_site} examples -> selected {keep.sum()} "
          f"(communication: {n_sites} scalars + the subset itself)")

    lab_of = {tuple(t): l for t, l in
              zip(toks.reshape(-1, L).tolist(), labs.reshape(-1, L).tolist())}
    sel_lab = np.asarray([lab_of[tuple(t)] for t in sel_tok.tolist()])
    n_b = max(len(sel_tok) // B, 1)
    sel_batches = [{"tokens": jnp.asarray(sel_tok[i*B:(i+1)*B]),
                    "labels": jnp.asarray(sel_lab[i*B:(i+1)*B])}
                   for i in range(n_b) if len(sel_tok[i*B:(i+1)*B]) == B]

    rng = np.random.default_rng(2)
    ridx = rng.choice(n_sites * per_site, size=len(sel_tok), replace=False)
    rt, rl = toks.reshape(-1, L)[ridx], labs.reshape(-1, L)[ridx]
    rand_batches = [{"tokens": jnp.asarray(rt[i*B:(i+1)*B]),
                     "labels": jnp.asarray(rl[i*B:(i+1)*B])}
                    for i in range(n_b) if len(rt[i*B:(i+1)*B]) == B]

    l_sel = train_on(sel_batches, cfg)
    l_rnd = train_on(rand_batches, cfg)
    print(f"final CE -- coreset-selected subset: {np.mean(l_sel[-10:]):.4f}"
          f"  vs uniform random subset: {np.mean(l_rnd[-10:]):.4f}")


if __name__ == "__main__":
    main()
