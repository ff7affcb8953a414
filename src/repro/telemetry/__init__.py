"""Structured run telemetry: named stages (device scope, host span and
timer under one name), throughput meters, and the machinery that turns
every benchmark artifact into a regression test.

See :mod:`repro.telemetry.logger` for the event schema and the
:class:`RunLogger` hierarchy; ``benchmarks/trajectory.py`` ingests the
artifacts this layer emits and ``benchmarks/gate.py`` gates CI on them.
"""
from .logger import (EVENT_KINDS, NULL, SCHEMA_VERSION, JsonlLogger,
                     MedianWindow, NullLogger, RateMeter, RecordingLogger,
                     RunLogger, Stage, calibrate, get_run_logger,
                     peak_rss_mb, register_run_logger, scope,
                     summarize_events, validate_event)

__all__ = [
    "EVENT_KINDS", "NULL", "SCHEMA_VERSION", "JsonlLogger", "MedianWindow",
    "NullLogger", "RateMeter", "RecordingLogger", "RunLogger", "Stage",
    "calibrate", "get_run_logger", "peak_rss_mb", "register_run_logger",
    "scope", "summarize_events", "validate_event",
]
