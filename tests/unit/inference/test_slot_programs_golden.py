"""The programs of the slot-holding twins that PR 50 (a run in a state-slot
geometry: Solar-Open2's prefill rows may continue one another) was to leave
word for word as they were: Solar-Open2's decode-bucket step and fused
program, and the one-row and four-row step programs of Phi-4-mini-flash,
Granite 4.0-H and MiniCPM-SALA, whose twins start every row from its slot.
Each is traced through the engine's own builder at a small size
(``InferenceEngineV2._aot_program``) and its jaxpr's digest held to
``slot_programs_golden.json``, which this file wrote on the parent of PR 50
(with ``_aot_program`` split out of ``_aot_lower`` there too, which touches no
program):

    PYTHONPATH=<a checkout> JAX_PLATFORMS=cpu python tests/unit/inference/test_slot_programs_golden.py \\
        > tests/unit/inference/slot_programs_golden.json

A PR that means to change one of these programs writes the file again and
says so; one that does not finds here that it did.  PR 56 added the last
entry, ``granitemoehybrid``: the decode-bucket step, the one-row mixed step
and the fused program of Granite 4.0-H with routed experts (the cell
``granite4hs_agent_turns`` at a small size), as that PR handed them in; the
dense sibling's two stood as they were.

The digests are taken in a process of their own (``python <this file>
--all``, once a module): a jaxpr's text names the jaxprs of the jitted
functions inside it by what the process traced before, so in a worker that
ran other twins' tests first the same program printed differently and the
comparison failed on the parent as on its child (found by PR 54, whose new
test files changed which files share a worker).
"""

import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.engine_v2 import build_cache_model
from deepspeed_tpu.inference.v2.scheduler import SchedulerConfig
from deepspeed_tpu.models.cache_zoo import cache_twin
from deepspeed_tpu.models.llama_cache import PagedKVConfig

from test_granite_moe_hybrid import CFG as GRANITE_MOE_CFG
from test_minicpm_sala import CFG as SALA_CFG
from test_slot_twins_golden import FAMILIES
from test_solar_open2 import CFG as SOLAR_CFG

CHUNK, BUCKET, FUSED = 32, 8, 4
DECODE, ONE_ROW, FOUR_ROWS = ((BUCKET, 1), ), ((BUCKET, 1), (1, CHUNK)), ((BUCKET, 1), (4, CHUNK))
#: twin -> (its small configuration, its page, the programs held)
HELD = {
    "solar_open2": (SOLAR_CFG, 16, (DECODE, ("multi", BUCKET, FUSED))),
    "phi4flash": (FAMILIES["phi4flash"][1], 16, (ONE_ROW, FOUR_ROWS)),
    "granitehybrid": (FAMILIES["granitehybrid"][1], 16, (ONE_ROW, FOUR_ROWS)),
    "minicpm_sala": (SALA_CFG, 8, (ONE_ROW, FOUR_ROWS)),       # a compressed key a page: the selection's stride
    "granitemoehybrid": (GRANITE_MOE_CFG, 16, (DECODE, ONE_ROW, ("multi", BUCKET, FUSED))),
}
CASES = [(twin, key) for twin, (_, _, keys) in HELD.items() for key in keys]
#: the digest of Solar-Open2's ``step:b8:c1:b4:c32`` on the parent of PR 50, which that PR meant to change
PARENT_SOLAR_FOUR_ROWS = "c878025b545252141364706ac50b7c3739ac39472c70a4afd135ef27b118a894"


@pytest.fixture(scope="module")
def engines():
    made = {}

    def engine(twin):
        if twin not in made:
            made[twin] = _engine(*HELD[twin][:2])
        return made[twin]

    return engine


@pytest.fixture(scope="module")
def fresh():
    """Every held program's digest, and the guard's two, from a process that has traced nothing else."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([os.path.join(here, "..", "..", ".."), here, os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run([sys.executable, os.path.abspath(__file__), "--all"], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout)


def _engine(cfg, page):
    """An engine over ``cfg`` under its twin's own initialisation: the
    programs are functions of shapes, the values are never read."""
    kv = PagedKVConfig(num_pages=64, page_size=page, max_pages_per_seq=20)
    model = build_cache_model(cfg, page)
    cache = cache_twin(cfg).init_cache(cfg, kv, jnp.float32, 2, CHUNK)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, ), jnp.int32),
                                 jnp.zeros((1, kv.max_pages_per_seq), jnp.int32), cache, jnp.ones((1, ), jnp.int32))
    sched = SchedulerConfig(token_budget=BUCKET + 4 * CHUNK, max_seqs=BUCKET, prefill_chunk=CHUNK, decode_bucket=BUCKET)
    return InferenceEngineV2(cfg, params, RaggedInferenceEngineConfig(
        kv=kv, scheduler=sched, max_new_tokens=8, decode_steps_per_dispatch=FUSED, enable_prefix_cache=False,
        kv_dtype=jnp.float32))


def digest(eng, key) -> str:
    """The program's jaxpr as text (an object's address, if one is printed, left out), hashed."""
    jitted, args = eng._aot_program(key)
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", str(jitted.trace(*args).jaxpr)).encode()).hexdigest()


def _golden():
    with open(os.path.join(os.path.dirname(__file__), "slot_programs_golden.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("twin, key", CASES, ids=[f"{twin}-{InferenceEngineV2._key_label(key)}" for twin, key in CASES])
def test_the_program_is_word_for_word_the_parents(engines, fresh, twin, key):
    assert key in engines(twin).step_shape_set()
    assert fresh[twin][InferenceEngineV2._key_label(key)] == _golden()[twin][InferenceEngineV2._key_label(key)]


def test_the_program_that_takes_a_run_is_not_among_them(fresh):
    """The guard of the guard: Solar-Open2's four-row step is the program the
    PR changed (its rows go one after another, the state handed on), and the
    digest tells it from the parent's."""
    four_rows, one_row = (fresh["guard"][InferenceEngineV2._key_label(key)] for key in (FOUR_ROWS, ONE_ROW))
    assert four_rows != PARENT_SOLAR_FOUR_ROWS
    assert four_rows != one_row


if __name__ == "__main__":      # the golden file's text; with --all the guard's two digests beside it
    made = {twin: _engine(cfg, page) for twin, (cfg, page, _) in HELD.items()}
    held = {twin: {InferenceEngineV2._key_label(key): digest(made[twin], key) for key in keys}
            for twin, (_, _, keys) in HELD.items()}
    if "--all" in sys.argv:
        held["guard"] = {InferenceEngineV2._key_label(key): digest(made["solar_open2"], key)
                         for key in (FOUR_ROWS, ONE_ROW)}
    print(json.dumps(held, indent=1))
