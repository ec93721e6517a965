"""Compiles that /debug/kernels counted between the window's start and its
end, over every xjit kernel (expected 0: nothing compiles in the window)."""


def read(spec: dict, ctx: dict):
    def total(k):
        return sum(e[spec.get("field", "compiles")] for e in k["kernels"])
    return total(ctx["kernels1"]) - total(ctx["kernels0"])
