"""host_replay_ms: host time per period of the serving loop's
``serve/next_batch`` spans (the replay source building the period's
batch), on the profiler's clock. Layer: host serving loop. Moves
events_per_s."""
import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx["trace"], "next_batch")
