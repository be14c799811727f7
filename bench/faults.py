"""Faults planted under the timed path, for the checks that the comparison
catches them: each wraps the compiled round step (``sess.step``) and
returns a step with the same signature. ``bench/control.py`` reads them on
the chip at a cell's own size; ``bench/tests/test_faults.py`` runs whole
runs with them on the CPU. The benchmark's own runs never plant one."""
import jax
import jax.numpy as jnp


def unchanged(sess, step):
    """The step returns its state unchanged."""
    def broken(state, batch, r):
        _, stats = step(jax.tree.map(jnp.copy, state), batch, r)
        return state, stats
    return broken


def half_batch(sess, step):
    """Half of every client's batch left out: the loss is the mean over the
    rest. The batch is halved by rows ([C, rows, seq]); where a client has
    one row, by the second half of that row's positions."""
    def broken(state, batch, r):
        mask = batch["clients"]["mask"]
        if mask.shape[1] >= 2:
            mask = mask.at[:, mask.shape[1] // 2:].set(0.0)
        else:
            mask = mask.at[..., mask.shape[-1] // 2:].set(0.0)
        batch = dict(batch, clients=dict(batch["clients"], mask=mask))
        return step(state, batch, r)
    return broken


def answer_altered(sess, step):
    """One value of the round's answer altered where it is produced: a
    client's eval pre-pass loss, off by 0.05 nats."""
    def broken(state, batch, r):
        state, stats = step(state, batch, r)
        stats = dict(stats, local_losses=stats["local_losses"].at[1].add(0.05))
        return state, stats
    return broken


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
