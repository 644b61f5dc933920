"""decode_ms.serve: device time of one dispatch of the engine's decode
program (`decode_masked` over paged KV)."""
from bench.readers import program_ms


def read(r):
    return program_ms(r, "jit_decode_masked")
