from readers import tokens_per_s as read  # noqa: F401
