"""Beyond-paper: packed KV-cache — decode memory-roofline effect per arch.

For each attention arch's decode_32k cell: KV bytes/step read at bf16 vs
packed int8/int4 (+ scale markers), and the resulting memory-term change
(decode reads the whole cache every step, so bytes ~ = the memory term).
"""
from repro.configs import base
from repro.launch import roofline

#: the chip whose HBM bandwidth turns bytes into the memory term
TARGET_KIND = "TPU v5 lite"

ARCHS = ["tinyllama-1.1b", "qwen1.5-110b", "yi-9b", "granite-8b",
         "grok-1-314b", "mixtral-8x7b", "internvl2-76b", "hymba-1.5b"]


def cache_bytes(cfg, rc, bits):
    """Total cache bytes: codes + per-(pos, head) f32 scale markers."""
    s = rc.seq_len if not cfg.sliding_window else min(rc.seq_len,
                                                      cfg.sliding_window)
    per_pos = cfg.n_kv_heads * cfg.hd * bits // 8
    if bits != 16:
        per_pos += cfg.n_kv_heads * 4          # scale marker per head row
    return rc.global_batch * cfg.n_layers * 2 * s * per_pos


def run():
    hbm_bw = roofline.peaks(TARGET_KIND).hbm_bw
    print(f"target: {TARGET_KIND}, {hbm_bw / 1e9:.0f} GB/s HBM per chip")
    print("arch,cache_GB_bf16,cache_GB_int8,cache_GB_int4,"
          "mem_term_ms_bf16_256chips,mem_term_ms_int8")
    for arch in ARCHS:
        cfg = base.load_arch(arch)
        rc = base.run_config_for("decode_32k", cfg)
        b16 = cache_bytes(cfg, rc, 16)
        b8 = cache_bytes(cfg, rc, 8)
        b4 = cache_bytes(cfg, rc, 4)
        t16 = b16 / 256 / hbm_bw * 1e3
        t8 = b8 / 256 / hbm_bw * 1e3
        print(f"{arch},{b16 / 1e9:.2f},{b8 / 1e9:.2f},{b4 / 1e9:.2f},"
              f"{t16:.2f},{t8:.2f}")


if __name__ == "__main__":
    run()
