"""The tests of ``test_row_groups.py`` that take a ``family``, over the two
families that hold a state slot a sequence."""

from test_row_groups import (  # noqa: F401 (collected here over this module's families)
    SLOT_HOLDING, families, test_nan_in_a_padding_slot_reaches_no_live_row_and_no_page_but_the_null_page,
    test_two_groups_give_the_rectangles_logits_and_arena)

pytest_generate_tests = families(SLOT_HOLDING)
