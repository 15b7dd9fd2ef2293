"""Share of dispatched token slots that were bucket padding."""
SOURCE = "flight"


def compute(src):
    pad = sum(s.get("padded_tokens", 0) for s in src.flight)
    real = sum(s.get("decode_rows", 0) + s.get("chunk_tokens", 0)
               for s in src.flight)
    return 100.0 * pad / (pad + real) if pad + real else None
