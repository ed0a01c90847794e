"""Faults planted under the timed path, to see ``correct`` come out false.
Used by ``test_chipbench_faults.py`` at CPU size and by
``calibrate.py fault`` on the chip; never by a benchmark run."""

from __future__ import annotations

FAULTS = ("state_unchanged", "half_batch_left_out", "token_altered")


def plant(monkeypatch, fault: str) -> None:
    """Break the program with ``monkeypatch.setattr`` (pytest's fixture
    or any object with that method):

    - ``state_unchanged``: decode returns the cache it was given;
    - ``half_batch_left_out``: decode's second half of the batch gets the
      first half's logits;
    - ``token_altered``: every decoded token's logits are shifted by one
      id before sampling.
    """
    import jax.numpy as jnp

    from repro.models import Model
    from repro.serving.engine import ContinuousEngine

    decode = Model.decode_step
    if fault == "state_unchanged":
        def step(self, params, token, cache, row_mask=None):
            return decode(self, params, token, cache, row_mask)[0], cache
        monkeypatch.setattr(Model, "decode_step", step)
    elif fault == "half_batch_left_out":
        def step(self, params, token, cache, row_mask=None):
            logits, new = decode(self, params, token, cache, row_mask)
            h = logits.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]]), new
        monkeypatch.setattr(Model, "decode_step", step)
    elif fault == "token_altered":
        post = ContinuousEngine._postdecode

        def postdecode(self, logits):
            return post(self, jnp.roll(logits, 1, axis=-1))
        monkeypatch.setattr(ContinuousEngine, "_postdecode", postdecode)
    else:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")


class Patch:
    """``monkeypatch.setattr`` outside pytest; the patch stays."""

    @staticmethod
    def setattr(obj, name, value):
        setattr(obj, name, value)
