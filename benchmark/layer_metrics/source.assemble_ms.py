"""source.assemble_ms: Serial cost of one source batch on the source thread, from inside the program: seconds of the spans source.assemble, source.note_batch and source.convert_labels in the window over the batches assembled."""

from harness import program_trace


def read(ctx):
    return program_trace.source_batch_ms(ctx)
