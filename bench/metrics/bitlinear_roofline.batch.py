from readers import bitlinear_roofline as read  # noqa: F401
