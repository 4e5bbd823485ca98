"""Bytes of latent cache one token holds in one layer AS STORED: the
engine's ``decode_state_bytes_kv_hw`` gauge over slots x rows a slot x
layers.  The published row is ``mla_bytes.row_bytes`` (1,152 B); what is
over it is padding to whole lane rows."""
MOVES = "serve_tokens_per_s"


def read(run):
    w = run["window"]
    kv = (w.get("state_bytes") or {}).get("kv")
    if not kv or not w.get("slots"):
        return None
    return kv / (w["slots"] * run["mix"]["max_len"]
                 * run["cfg"]["num_hidden_layers"])
