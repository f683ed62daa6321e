"""Shared utilities: metrics, timing, profiling, backend pinning,
atomic publication."""

from .atomicio import atomic_publish
from .metrics import AverageMeter, cross_entropy_loss, top_k_accuracy
from .platform import announce_devices, compile_cache_dir, pin_platform
from .profiling import SPAN_NAMES, SpanRecorder, device_span, trace

__all__ = ["AverageMeter", "SPAN_NAMES", "SpanRecorder", "announce_devices",
           "atomic_publish", "compile_cache_dir", "cross_entropy_loss",
           "device_span", "pin_platform", "top_k_accuracy", "trace"]
