"""What a Mamba-2 layer's state recurrence must do, counted from the rows a
step held: the operations and bytes a roofline share of the update kernel
(``dynamo_tpu/ops/mamba2.py``, op ``mamba2_decode_update`` in the device
trace) and of the chunked scan holds its measured time against.

H heads of P channels, a state of N a channel: S is H x P x N.

- **The update** of one row (one token) in one layer: S <- a S + dx (x) B is
  a multiply, a multiply and an add an entry, y = S C a multiply and an add:
  5 H P N operations. The bytes it MUST move are the row's state in and out
  (float32), its inputs (the decay and dt·x a channel, B and C) and y out —
  never more, so that the share cannot pass 100%: a padding row, the dump
  slot and a second read of anything are the kernel's own cost.
- **The scan** of a chunk of T tokens in one layer, in blocks of Q: inside a
  block the masked product C Bᵀ (2 Q N a token) and its product with dt·x
  (2 Q H P a token), between blocks the block's state (2 H P N a token to
  build, 2 H P N a token to read). Its bytes are the chunk's inputs and
  outputs once (x, B, C, dt in, y out) and the row's state in and out once.

The update is one Pallas launch a run of Mamba-2 layers a step program,
named ``mamba2_decode_update_l<first>x<layers>_<program>`` so that a launch's
time can be held against the rows of exactly the steps and layers that ran
it; the scan is XLA's own fusions of the einsums (no one op's name holds it,
so no reader can hold its time against this count yet).
"""

F32 = 4


def update_ops(rows: int, H: int, P: int, N: int) -> int:
    return 5 * rows * H * P * N


def update_bytes(rows: int, H: int, P: int, N: int) -> int:
    state = 2 * H * P * N * F32                 # in and out
    inputs = (2 * H * P + 2 * N) * F32          # decay, dt·x, B, C
    return rows * (state + inputs + H * P * F32)  # + y


def scan_ops(tokens: int, H: int, P: int, N: int, block: int = 128) -> int:
    q = min(block, tokens)
    return tokens * (2 * q * N + 2 * q * H * P + 4 * H * P * N)


def scan_bytes(tokens: int, rows: int, H: int, P: int, N: int,
               itemsize: int = 2) -> int:
    per_token = (H * P + 2 * N + H) * itemsize + H * P * F32  # in, y out
    return tokens * per_token + rows * 2 * H * P * N * F32


def roofline_seconds(ops: int, nbytes: int, flops_per_s: float,
                     bytes_per_s: float) -> float:
    """The least time the work can take: the slower of computing it at the
    peak and of moving its bytes at the memory's bandwidth."""
    return max(ops / flops_per_s, nbytes / bytes_per_s)
