"""How the program's kernels are told apart in a device trace."""

from chip.tracing import PALLAS


def is_attention_kernel(op_name: str) -> bool:
    """An event of the fused temporal-attention kernel, forward or
    backward. The trace names a Mosaic (Pallas) call only by its HLO
    instruction (``jvp__.1``), so every Mosaic call counts: in the TGAT and
    TGN steps the fused attention calls are the only ones. A later Pallas
    kernel on these steps would add its time here and lower the share,
    never raise it; separating it is a change to the benchmark."""
    return op_name.endswith(PALLAS)
