"""What every run does whatever the cell: find the chips, place the compile
cache, count compilations, read the memory peak, print the result line."""
import gc
import json
import os
import sys

from . import cells


def place_compile_cache():
    """Before jax is imported: the persistent cache goes where
    JAX_COMPILATION_CACHE_DIR says, else to a fixed place in the checkout.
    The program reads the same variable, so both share one directory."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(cells.ROOT, ".xla_cache"))
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def configure_jax():
    import jax

    # persist every program, however quick its compile: a run after the
    # first then finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def find_devices(chips, rehearse):
    """The cell's devices.  Without ``rehearse`` anything but a TPU with
    enough chips ends the run, non-zero, with no result line."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if not rehearse and d0.platform != "tpu":
        sys.exit("benchmark: this run needs a TPU, and JAX found platform %r "
                 "(%s, %d device(s)); --rehearse runs a toy on the cpu and "
                 "prints no device metric"
                 % (d0.platform, d0.device_kind, len(devices)))
    if len(devices) < chips:
        sys.exit("benchmark: the cell needs %d chip(s), JAX found %d"
                 % (chips, len(devices)))
    return devices[:chips]


class CompileCounter:
    """Counts what JAX compiles (or fetches from its cache) from now on."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event in self._EVENTS:
            self.count += 1


def loaded_programs(device):
    """[(temporaries in bytes, module name)] of the programs the client
    holds loaded now, as XLA's ``memory_analysis`` counts them for one
    device; None where the client cannot say."""
    try:
        out = []
        for ex in device.client.live_executables():
            mods = ex.hlo_modules()
            out.append((int(ex.get_compiled_memory_stats().temp_size_in_bytes),
                        mods[0].name if mods else "?"))
        return out
    except Exception as e:                 # noqa: BLE001 (a reading only)
        say("loaded programs not readable: %r" % (e,))
        return None


def memory_peak_bytes(devices):
    """Peak on the fullest chip; None where the backend reports none.

    Read while the window's state is still on the device.  The TPU runtime
    counts live arrays under ``bytes_in_use`` / ``peak_bytes_in_use`` and
    sets the loaded programs' temporaries (activations, scratch) aside
    under ``bytes_reserved``: ``peak_bytes_in_use`` never shows them
    (PERF.md section 2 has the readings).  A step holds its arrays and its
    own temporaries at once, so the peak is the arrays live now plus the
    temporaries of the largest program loaded (the step, by the name
    printed), never more than the runtime has set aside, and never under
    the arrays' own peak.  Every counter is printed beside the result."""
    programs = loaded_programs(devices[0])
    largest = max(programs, default=(0, "none")) if programs is not None \
        else None
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            continue
        scratch = int(stats.get("bytes_reserved", 0))
        if largest is not None and scratch:
            scratch = min(scratch, largest[0])
        peaks.append(max(int(stats["peak_bytes_in_use"]),
                         int(stats.get("bytes_in_use", 0)) + scratch))
        say("memory of device %d: peak_bytes_in_use %d, bytes_in_use %d, "
            "bytes_reserved %d, bytes_limit %d" % (
                d.id, stats["peak_bytes_in_use"],
                stats.get("bytes_in_use", 0), stats.get("bytes_reserved", 0),
                stats.get("bytes_limit", 0)))
    if programs is not None:
        say("%d programs loaded; temporaries: largest %d (%s), all together "
            "%d" % (len(programs), largest[0], largest[1],
                    sum(t for t, _n in programs)))
    return max(peaks) if peaks else None


def device_record(devices, extra=None):
    d0 = devices[0]
    rec = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices),
           "memory_peak_bytes": memory_peak_bytes(devices)}
    rec.update(extra or {})
    return rec


def quiet_host():
    """Before a window: nothing left for the collector to find, and the
    collector off until ``unquiet_host``."""
    gc.collect()
    gc.freeze()
    gc.disable()


def unquiet_host():
    gc.enable()
    gc.unfreeze()


def say(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def print_result(result):
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
