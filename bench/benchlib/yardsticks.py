"""Frozen yardsticks: the chip's published peaks and the model operations
of one U-Net forward, computed from shapes.  Later changes to the program
cannot move them."""
from __future__ import annotations

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.  A device kind missing here is an error.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def _taps(n_in: int, k: int, stride: int) -> int:
    """Kernel taps along one axis that land inside the image, summed over
    the outputs of a SAME-padded convolution (padding taps do no work)."""
    n_out = -(-n_in // stride)
    pad = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for d in range(k)
               if 0 <= o * stride + d - pad < n_in)


def _conv(s, k, cin, cout, stride=1):
    """Operations of a SAME convolution over an s×s input."""
    return 2 * _taps(s, k, stride) ** 2 * cin * cout


def unet_forward_flops(m: dict) -> int:
    """Multiply-add operations (×2) of one image's U-Net forward: every
    convolution, dense layer and attention product.  Normalisation,
    activations and additions are left out (under 1% of the total)."""
    ch, td, r = m["base_channels"], m["time_dim"], m["image_size"]
    mults, nres = m["channel_mults"], m["n_res_blocks"]
    attn_at = set(m["attn_resolutions"])

    def res(cin, cout, s):
        f = _conv(s, 3, cin, cout) + 2 * td * cout + _conv(s, 3, cout, cout)
        return f + (_conv(s, 1, cin, cout) if cin != cout else 0)

    def attn(c, s):
        n = s * s
        return 2 * n * c * 3 * c + 2 * 2 * n * n * c + 2 * n * c * c

    flops = 2 * 2 * td * td + _conv(r, 3, m["in_channels"], ch)
    cur, chans = ch, [ch]
    for li, mult in enumerate(mults):
        for _ in range(nres):
            flops += res(cur, ch * mult, r)
            cur = ch * mult
            if r in attn_at:
                flops += attn(cur, r)
            chans.append(cur)
        if li < len(mults) - 1:
            flops += _conv(r, 3, cur, cur, stride=2)
            chans.append(cur)
            r //= 2
    flops += 2 * res(cur, cur, r) + attn(cur, r)
    for li, mult in list(enumerate(mults))[::-1]:
        for _ in range(nres + 1):
            flops += res(cur + chans.pop(), ch * mult, r)
            cur = ch * mult
            if r in attn_at:
                flops += attn(cur, r)
        if li > 0:
            r *= 2
            flops += _conv(r, 3, cur, cur)
    return flops + _conv(r, 3, cur, m["in_channels"])

