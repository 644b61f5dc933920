"""chunk_ms.serve: device time of one dispatch of the engine's chunked
prefill program (`chunk_masked`)."""
from bench.readers import program_ms


def read(r):
    return program_ms(r, "jit_chunk_masked")
