"""The least time the chip could take for a call: the larger of operations
over the peak rate of their type and bytes over the memory's rate."""


def bound_seconds(ops: float, byts: float, peaks: dict, dtype: str):
    by_ops = ops / peaks["flops_per_s"][dtype]
    by_bytes = byts / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("operations" if by_ops >= by_bytes else "bytes")
