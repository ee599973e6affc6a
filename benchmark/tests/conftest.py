"""The ONE pin of an accepted benchmark test that PR 44 made stale and
might not edit (a PR edits no file the benchmark already has; ISSUE 44
asked for the two metrics the pin excludes): a strict xfail that names
the `benchmark` PR's repair, and the test that holds the rest of its
assertions meanwhile.  An unexpected pass fails, so it is noticed.

Closed: no later PR adds an entry (a way to silence an accepted test is
not a mechanism to keep; ``test_mobius_sblock.py::
test_the_stale_registry_is_closed`` fails if ``STALE`` is anything but
this).  ROADMAP B9's `benchmark` PR appends the two names to
``test_mobius.py`` and deletes this file and the stand-in test with it."""

import pytest

STALE = {
    "test_mobius.py::test_the_action_is_the_configurations_and_the_cell_is_listed":
        "pins the Möbius cell's own per-layer metrics to PR 42's eight; "
        "PR 44 added mobius_sblock_kernel_us and "
        "mobius_sblock_kernel_roofline (append the two names: ROADMAP B9); "
        "every other assertion of it runs in test_mobius_sblock.py::"
        "test_the_accepted_listing_test_holds_without_the_two_new_names",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for node, why in STALE.items():
            if item.nodeid.endswith(node):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
