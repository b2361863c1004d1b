"""Operations and bytes, from shapes, of the kernel the ``lfm2_moe``
family adds (``flops.py`` has the rules: what the algorithm requires,
a multiply-add is two operations, recomputation does not count).
"""


def short_conv(rows: float, channels: int, taps: int,
               backward: bool = False) -> float:
    """``rows`` tokens through ``C * conv(B * X)``: per token and channel
    the product ``B * X``, a multiply-add a tap, the gate ``C *``:
    ``2 taps + 2``. Backward: ``dz = g * C``, ``dC = g * z``, the taps
    over ``dz`` (a multiply-add each), ``dB`` and ``dX``, and the taps'
    own gradient (a multiply-add a tap): ``4 taps + 4`` — ``z`` made
    again is recomputation and is not counted."""
    per = 4.0 * taps + 4 if backward else 2.0 * taps + 2
    return per * rows * channels


def short_conv_bytes(rows: float, channels: int, taps: int,
                     itemsize: int, backward: bool = False) -> float:
    """Least bytes to and from memory: forward reads B, C, X and writes
    one; backward reads those and the result's cotangent and writes
    three. The taps and their gradient (``taps * channels`` float32 a
    call) are left aside: a 32,768th of the rest at the cell's size."""
    one = float(rows) * channels * itemsize
    return 7.0 * one if backward else 4.0 * one
