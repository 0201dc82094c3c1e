"""host_upload_ms: host time per period of the serving loop's
``serve/stage`` spans (``HostIngestRing.stage`` putting the batch on the
device), on the profiler's clock. Layer: host serving loop. Moves
events_per_s."""
import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx["trace"], "stage")
