"""Operations and bytes a step must move, computed from a configuration's
shapes; the roofline readers divide them by the device's peaks."""
