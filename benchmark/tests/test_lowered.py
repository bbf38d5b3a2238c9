"""`benchmark/lowered.py compare`: two lowered texts differ in nothing, in
Mosaic payloads alone, or in the program."""

import base64

from benchmark import lowered


def line(payload: bytes, rest: str = "%1 = custom_call") -> str:
    blob = base64.b64encode(payload).decode().rstrip("=")
    return rest + ' {backend_config = "{\\22body\\22: \\22' + blob + '\\22}"}'


def varint(n: int) -> bytes:
    return ((n << 2) | 1).to_bytes(2, "little")


def test_compare_texts():
    head = "module @jit_step {\n  %0 = stablehlo.add %a, %b\n"
    stack_a = b"MLIR" + varint(274) + b"kernel body"
    stack_b = b"MLIR" + varint(260) + b"kernel body"
    a, b = head + line(stack_a), head + line(stack_b)
    assert lowered.compare_texts(a, a)["verdict"] == "identical"
    # a line number inside a payload's call stack moved: the same program
    found = lowered.compare_texts(a, b)
    assert found["verdict"] == "payload_only" and found["payloads"] == 1
    assert found["bytes"] == 1 and (274, 260) in found["values"]
    # payloads of another length are still payloads, with no values to pair
    longer = lowered.compare_texts(a, head + line(stack_b + b"!"))
    assert longer == {"verdict": "payload_only", "payloads": 1, "bytes": 1,
                      "values": []}
    # anything outside a payload is another program
    assert lowered.compare_texts(
        a, head.replace("add", "mul") + line(stack_a))["verdict"] == "differs"
    assert lowered.compare_texts(
        a, head + line(stack_a, "%1 = other_call"))["verdict"] == "differs"
    assert lowered.compare_texts(a, a + "\n}")["verdict"] == "differs"
