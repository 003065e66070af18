from readers import decode_tick_ms_p50 as read  # noqa: F401
