"""Operations and bytes that an algorithm needs for one call, from its
shapes: the numerator of a roofline share or a utilization."""
