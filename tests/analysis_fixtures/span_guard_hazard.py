"""Fixture for the span-outside-guard rule: a tracing Span opened around a
kernel dispatch that bypasses guard.supervised must fire (the span would
record wall time the watchdog can abandon); the supervised-inside-span form,
span-free dispatches (naked-dispatch's beat, not this rule's), and
suppressed sites must not."""

import functools

from open_simulator_tpu.ops import kernels
from open_simulator_tpu.resilience import guard
from open_simulator_tpu.utils.trace import Span

tables = carry = active = pg = fn = vd = sc = None


def span_around_naked_dispatch():
    # finding: the span measures a dispatch the watchdog cannot contain
    with Span("dispatch"):
        return kernels.schedule_batch(tables, carry, pg, fn, vd)


def scope_span_around_naked_dispatch():
    # finding: simonscope live spans are the same hazard
    with sc.span("kernel:wave"):
        c, counts, placed = kernels.schedule_wave(tables, carry, 0, 8, False)
    return counts


def span_with_annotation_around_fanout():
    # finding: nested statements inside the with-body are still covered
    with Span("probe") as span:
        span.annotate("phase", "setup")
        out = kernels.probe_serial_fanout(tables, carry, active, pg, fn, vd)
    return out


def span_around_supervised_is_fine():
    # clean: the span may time the SUPERVISED call — the watchdog contains
    # the dispatch, the span just reads the wall clock around it
    with Span("dispatch"):
        return guard.supervised(
            lambda: kernels.schedule_batch(tables, carry, pg, fn, vd),
            site="dispatch", pods=8)


def span_around_supervised_partial_is_fine():
    # clean: functools.partial resolution matches guard.supervised's
    with sc.span("kernel:serial"):
        call = functools.partial(kernels.schedule_group_serial, tables, carry)
        return guard.supervised(call, site="dispatch", pods=8)


def span_without_dispatch_is_fine():
    # clean: spans around host work are the normal case
    with Span("encode"):
        return [tables, carry]


def suppressed_span_dispatch():
    with Span("offline"):
        # simonlint: ignore[span-outside-guard, naked-dispatch] -- offline audit harness, no wedge exposure
        return kernels.probe_wave_fanout(tables, carry, active, 0, 8, False)
