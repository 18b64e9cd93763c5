"""The tests of ``test_row_groups_engine.py`` that take a ``family``, over the
two families that hold a state slot a sequence."""

from test_row_groups_engine import (  # noqa: F401 (collected here over this module's ``family``)
    SLOT_HOLDING, families, test_both_serving_ticks_emit_the_row_at_a_time_streams_and_compile_nothing,
    test_generate_emits_the_row_at_a_time_streams, test_the_step_record_of_a_two_group_step)

family = families(SLOT_HOLDING)
