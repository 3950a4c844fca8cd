"""How the trace names the program's parts: the served programs
(XLA modules) and the Pallas kernel. These are the only names the
benchmark takes from the program."""


def is_decode(module: str) -> bool:
    """The blocked-decode program (`registry.make_block_decode`'s `run`)."""
    return module.startswith("jit_run")


def is_prefill(module: str) -> bool:
    """The engine's prefill-chunk program (a wrapped lambda)."""
    return module.startswith("jit_wrapped") or module.startswith(
        "jit__lambda")


def is_fused_mm(op: str) -> bool:
    """`kernels.fused.fused_dequant_mm`'s Pallas kernel."""
    return "fused_dequant" in op
