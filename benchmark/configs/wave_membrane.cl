
__kernel void waveStep(__global float* u0, __global float* u1,
                       __global float* frame,
                       int width, int height, float c2) {
    int i = get_global_id(0);
    int x = i % width;
    int y = i / width;
    if (x == 0 || x == width - 1 || y == 0 || y == height - 1) {
        frame[i] = 0.0f;    /* clamped boundary */
    } else {
        float lap = u1[i - 1] + u1[i + 1] + u1[i - width] + u1[i + width]
                    - 4.0f * u1[i];
        frame[i] = 2.0f * u1[i] - u0[i] + c2 * lap;
    }
}
__kernel void rotate(__global float* u0, __global float* u1,
                     __global float* frame,
                     int width, int height, float c2) {
    int i = get_global_id(0);
    u0[i] = u1[i];
    u1[i] = frame[i];
}
