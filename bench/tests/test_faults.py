"""A whole run, past the harness's look for a chip, with the timed path
broken underneath: ``correct`` has to come out false.

The faults a serving cell can have: a decode step that returns its state
unchanged (the new token's K/V never lands in the page pool), half of the
batch left out (every other row not computed: it repeats its last token),
and a token altered where it is produced (the admit program's first
token).  Both cells run on one chip, so there is no exchange between chips
to leave out.  The sound run is checked too, so a false ``correct`` is the
fault's doing.  Here every finished request is compared, so that which
slots a request happened to take cannot hide a fault."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.spec import load_limits
from bench.tests.conftest import CELLS, tiny_cell


def _stale_state(real):
    def step(params, pstate, key, mirrors, table):
        _, toks, key, mirrors = real(params, pstate, key, mirrors, table)
        return pstate, toks, key, mirrors
    return step


def _half_batch(real):
    def step(params, pstate, key, mirrors, table):
        pstate, toks, key, new = real(params, pstate, key, mirrors, table)
        toks = toks.at[1::2].set(mirrors["tok"][1::2])
        return pstate, toks, key, dict(new, tok=toks)
    return step


def _altered_token(real):
    def admit(params, pstate, batch, key, mirrors):
        pstate, tok, key, mirrors = real(params, pstate, batch, key, mirrors)
        tok = (tok + 1) % 512
        slot = batch["slot"]
        return pstate, tok, key, dict(mirrors,
                                      tok=mirrors["tok"].at[slot].set(tok[0]))
    return admit


FAULTS = {
    "state_unchanged": ("paged_decode_program", "_make_paged_decode_program",
                        _stale_state),
    "half_batch": ("paged_decode_program", "_make_paged_decode_program",
                   _half_batch),
    "token_altered": ("paged_admit_program", "_make_paged_admit_program",
                      _altered_token),
}


def _run(name, widths=False, control=False, seconds=3.0):
    cell = tiny_cell(name, widths=widths)
    lim = dict(load_limits(name),
               sample={"min_tokens": 10**9, "max_requests": 10**6})
    return harness.run_cell(cell, 2**32 + 9, seconds, False, time.perf_counter(),
                            require_tpu=False, limits=lim, control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["check"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_caught(name, fault, monkeypatch):
    from repro.serve import programs
    public, builder, wrap = FAULTS[fault]
    make = getattr(programs, builder)

    def broken(cfg, policy, *args):
        return jax.jit(wrap(make(cfg, policy, *args)))

    monkeypatch.setattr(programs, public, broken)
    out = _run(name)
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The control (the reference in fp8, its first choices in the served
    tokens' place) fails the cell's own comparison, at the configuration's
    widths cut to two layers, where the program's own reading passes."""
    out = _run(name, widths=True, control=True, seconds=12.0)
    limit = load_limits(name)["widest_logit_gap"]["limit"]
    assert out["readings"]["program_widest_logit_gap"] <= limit, out
    assert out["check"]["widest_logit_gap"]["limit"] == limit
    assert not out["correct"], out["check"]
