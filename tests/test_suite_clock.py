"""The suite's clock (``conftest.py::CALL_CEILING_S``): a test that is not
marked ``slow`` and whose call took longer than the ceiling is reported
failed, under its own name. Nothing here sleeps: the hook is handed a
call that says how long it took."""

import pytest
from conftest import CALL_CEILING_S


def _report(item, seconds, when="call"):
    call = pytest.CallInfo(result=None, excinfo=None, start=0.0,
                           stop=seconds, duration=seconds, when=when,
                           _ispytest=True)
    return item.ihook.pytest_runtest_makereport(item=item, call=call)


@pytest.mark.parametrize("seconds, outcome", [
    (CALL_CEILING_S - 1.0, "passed"), (CALL_CEILING_S + 1.0, "failed")],
    ids=["under_the_ceiling", "over_it"])
def test_a_test_that_outgrows_the_suite_fails_by_name(
        request, seconds, outcome):
    report = _report(request.node, seconds)
    assert report.outcome == outcome
    if outcome == "failed":
        assert str(report.longrepr) == (
            f"took {seconds:.0f} s: make it smaller or mark it slow "
            f"(see README, Running it)")


def test_the_ceiling_is_the_calls_and_spares_what_is_marked_slow(request):
    over = CALL_CEILING_S + 1.0
    assert _report(request.node, over, when="setup").outcome == "passed"
    request.node.add_marker("slow")     # this item only, and from here on
    assert _report(request.node, over).outcome == "passed"
